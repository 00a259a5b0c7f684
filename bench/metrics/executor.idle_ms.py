"""executor.idle_ms: device idle under the executor's spans
(``executor.put``, ``executor.launch``, ``executor.fetch``) per dispatch,
from the profiler trace."""
from bench import spans


def read(rec):
    return spans.idle_ms_per_dispatch(rec, spans.EXECUTOR)
