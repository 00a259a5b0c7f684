"""build_s: host clock around the index build, ending when both graphs'
adjacency is on the device (``block_until_ready``)."""


def read(rec):
    return rec.build_s
