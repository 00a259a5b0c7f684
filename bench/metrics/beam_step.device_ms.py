"""beam_step.device_ms: device time of the walk kernel's events in the
traced window, per dispatch."""
from bench import tracing


def read(rec):
    if rec.trace is None or not rec.dispatches:
        return None
    s = rec.trace.kernel_s(tracing.WALK_KERNEL)
    return None if not s else s / len(rec.dispatches) * 1e3
