"""Device milliseconds per run of the vision program, from the trace."""
from harness.common import stage_events


def read(run):
    ev = stage_events(run, "vision")
    if not ev:
        return None
    return 1e3 * sum(e.dur for e in ev) / len(ev)
