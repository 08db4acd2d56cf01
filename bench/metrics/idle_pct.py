"""Share of the traced window in which no operation runs on the device,
averaged over the chips the cell uses."""
from harness import trace as T


def read(run):
    w = run.window
    if w.trace is None or not w.trace.ops:
        return None
    a, b = w.trace_span
    busy = sum(T.busy(ops, a, b) for ops in w.trace.ops.values())
    return 100.0 * (1.0 - busy / (len(w.trace.ops) * (b - a)))
