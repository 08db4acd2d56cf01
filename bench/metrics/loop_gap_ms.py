"""Milliseconds from the end of one ``engine.tick`` to the start of the
next while work is pending: the mean over the window's consecutive tick
pairs with no ``frontend.park`` between them. This is the front end's
drain and pump and whatever else the event loop runs between ticks. Read
from the engine's span recorder (``Request.trace``); None where the program
records no spans or the ring dropped some of the window's."""
import bisect


def read(run):
    w = run.window
    rec = next((s.request.trace for s in run.steps
                if getattr(s.request, "trace", None) is not None), None)
    if rec is None or not rec.intact_since(w.open):
        return None
    spans = rec.spans(w.open, w.close)
    ticks = sorted((s.t0, s.t1) for s in spans if s.name == "engine.tick")
    parks = sorted(s.t0 for s in spans if s.name == "frontend.park")
    gaps = [b0 - a1 for (_, a1), (b0, _) in zip(ticks, ticks[1:])
            if bisect.bisect_left(parks, a1) == bisect.bisect_left(parks,
                                                                   b0)]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
