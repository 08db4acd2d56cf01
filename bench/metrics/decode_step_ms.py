"""Device milliseconds per decode step: the fused tick programs' device
time, the mean over the cell's chips, over the window's device steps
(``EngineStats.device_steps``)."""
from harness.common import chips, stage_events


def read(run):
    ev = stage_events(run, "tick")
    steps = run.window.counters["device_steps"]
    if not ev or not steps:
        return None
    return 1e3 * sum(e.dur for e in ev) / len(chips(run)) / steps
