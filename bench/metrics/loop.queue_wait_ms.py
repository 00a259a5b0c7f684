"""loop.queue_wait_ms: mean of dispatch time - due time over the window's
responses (ServeLoop's queueing and coalescing)."""
import numpy as np


def read(rec):
    return float(np.nanmean(rec.dispatch_t - rec.arrival_t) * 1e3)
