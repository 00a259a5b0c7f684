"""executor.idle_ms: device idle under the executor's spans
(``executor.put``, ``executor.launch``, ``executor.fetch``) per dispatch,
from the profiler trace."""
from bench import tracing

SPANS = ("executor.put", "executor.launch", "executor.fetch")


def read(rec):
    return tracing.idle_ms_per_dispatch(rec, SPANS)
