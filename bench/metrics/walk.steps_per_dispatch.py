"""walk.steps_per_dispatch: beam_step launches (while_loop iterations of
every walk, both walks for ip-NSW+) per dispatch of the window, from the
program's ``walk_steps_total`` counter."""
from bench import spans


def read(rec):
    steps = spans.counter(rec, "walk_steps_total")
    if steps is None or not rec.dispatches:
        return None
    return steps / len(rec.dispatches)
