"""walk.evals_per_query: similarity evaluations per live request (both
walks for ip-NSW+), from the evals BucketExecutor.run returns."""


def read(rec):
    rows = sum(d.rows for d in rec.dispatches)
    if not rows:
        return None
    return sum(d.evals for d in rec.dispatches) / rows
