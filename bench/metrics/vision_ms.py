"""Device milliseconds per run of the vision program, from the trace: the
mean over every run on every chip of the cell, which is the mean over the
chips."""
from harness.common import stage_events


def read(run):
    ev = stage_events(run, "vision")
    if not ev:
        return None
    return 1e3 * sum(e.dur for e in ev) / len(ev)
