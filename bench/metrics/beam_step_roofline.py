"""beam_step_roofline: the walk kernel's least time over its device time
in the traced window, in %.

The least time is max(ops / peak ops, bytes / peak bandwidth) for the
evaluations of the window (``tracing.walk_work``): 2d operations and 4d
bytes per evaluation, d the catalog's unpadded width, so lane padding
counts as waste."""
from bench import tracing


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    s = rec.trace.kernel_s(tracing.WALK_KERNEL)
    if not s:
        return None
    evals = sum(d.evals for d in rec.dispatches)
    ops, nbytes = tracing.walk_work(evals, rec.dim)
    least = max(ops / rec.peaks["bf16_flops_per_s"],
                nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s
