"""p95_ms: 95th percentile over every request due in the window of due
time -> response, on the wall clock; requests finishing after the window
count."""
from bench import yardstick


def read(rec):
    return yardstick.p95_ms(rec.finish_t - rec.arrival_t)
