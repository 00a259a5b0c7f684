"""recall_at_10: mean recall@10 of the served ids against the benchmark's
own exact top-10, over every request of the window."""


def read(rec):
    return float(rec.recall.mean())
