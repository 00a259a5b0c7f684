"""executor.dispatch_ms: mean host wall time of one BucketExecutor.run
call (pad, transfer, the bucket program, results back), measured by the
benchmark's wrapper."""
import numpy as np


def read(rec):
    if not rec.dispatches:
        return None
    return float(np.mean([d.end - d.start for d in rec.dispatches]) * 1e3)
