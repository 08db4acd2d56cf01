"""Share of the traced window in which the first chip is idle and no span
of the program is open: the idle that the program cannot name, so
``idle_pct`` less this is the idle it can. The program's spans come from
its recorder (``Request.trace``) on ``time.perf_counter()``; they are moved
onto the trace's clock by the window's close, which both clocks mark
microseconds apart (``Window.close`` and the end of ``bench.window``).
None where the program records no spans or the ring dropped some of the
window's."""
from harness import trace as T


def read(run):
    w = run.window
    rec = next((s.request.trace for s in run.steps
                if getattr(s.request, "trace", None) is not None), None)
    if rec is None or not rec.intact_since(w.open):
        return None
    if w.trace is None or not w.trace.ops:
        return None
    a, b = w.trace_span
    shift = b - w.close
    named = T.union([T.Event(s.name, s.t0 + shift, s.t1 + shift)
                     for s in rec.spans() if s.t1 + shift > a
                     and s.t0 + shift < b])
    unnamed, j = 0.0, 0
    for g0, g1 in sorted(T.gaps(w.trace.ops[min(w.trace.ops)], a, b)):
        unnamed += g1 - g0
        while j < len(named) and named[j][1] <= g0:
            j += 1
        k = j
        while k < len(named) and named[k][0] < g1:
            unnamed -= min(g1, named[k][1]) - max(g0, named[k][0])
            k += 1
    return 100.0 * unnamed / (b - a)
