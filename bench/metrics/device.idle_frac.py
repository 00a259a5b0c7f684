"""device.idle_frac: 1 - (union of the device's op intervals / traced
window), from the profiler trace."""


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    return 1.0 - rec.trace.busy_s / rec.trace.window_s
