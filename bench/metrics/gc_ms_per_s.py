"""Milliseconds of garbage collection per second of the window: the
``host.gc`` spans (one per collection, from ``gc.callbacks``) that lie in
the window, over its length. Read from the engine's span recorder
(``Request.trace``); None where the program records no spans or the ring
dropped some of the window's."""


def read(run):
    w = run.window
    rec = next((s.request.trace for s in run.steps
                if getattr(s.request, "trace", None) is not None), None)
    if rec is None or not rec.intact_since(w.open):
        return None
    gc_s = sum(s.t1 - s.t0 for s in rec.spans(w.open, w.close, "host.gc"))
    return 1e3 * gc_s / (w.close - w.open)
