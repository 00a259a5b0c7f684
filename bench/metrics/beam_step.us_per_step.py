"""beam_step.us_per_step: the walk kernel's device time in the traced
window over its launches (the program's ``walk_steps_total``), in us."""
from bench import spans, tracing


def read(rec):
    steps = spans.counter(rec, "walk_steps_total")
    if rec.trace is None or not steps:
        return None
    s = rec.trace.kernel_s(tracing.WALK_KERNEL)
    return s / steps * 1e6 if s else None
