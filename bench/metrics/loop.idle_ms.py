"""loop.idle_ms: device idle under the serving loop's own work
(``loop.form``, ``loop.demux``) per dispatch, from the profiler trace;
sleeps, admission and collections count in neither idle metric."""
from bench import tracing

SPANS = ("loop.form", "loop.demux")


def read(rec):
    return tracing.idle_ms_per_dispatch(rec, SPANS)
