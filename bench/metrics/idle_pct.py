"""Share of the traced window in which no operation runs on the device,
averaged over the chips the cell uses."""
from harness import trace as T
from harness.common import chips


def read(run):
    w, devs = run.window, chips(run)
    if not devs:
        return None
    a, b = w.trace_span
    busy = sum(T.busy(w.trace.ops[d], a, b) for d in devs)
    return 100.0 * (1.0 - busy / (len(devs) * (b - a)))
