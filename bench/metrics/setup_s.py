"""setup_s: process start to the window's first instant (catalog, build,
warmup, compiles), on the host clock."""


def read(rec):
    return rec.setup_s
