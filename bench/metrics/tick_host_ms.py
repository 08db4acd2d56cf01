"""Host milliseconds the engine adds to each tick: the mean, over the
window's ``engine.tick`` spans, of the tick's length less the device waits
inside it (``engine.readback``, ``engine.vision``, ``engine.first_token``).
Read from the engine's span recorder (``Request.trace``); None where the
program records no spans or the ring dropped some of the window's."""

WAITS = ("engine.readback", "engine.vision", "engine.first_token")


def read(run):
    w = run.window
    rec = next((s.request.trace for s in run.steps
                if getattr(s.request, "trace", None) is not None), None)
    if rec is None or not rec.intact_since(w.open):
        return None
    ring = rec.spans()
    by_seq = {s.seq: s for s in ring}
    ticks = {s.seq: s for s in rec.spans(w.open, w.close)
             if s.name == "engine.tick"}
    if not ticks:
        return None
    waits = dict.fromkeys(ticks, 0.0)
    for s in ring:
        if s.name not in WAITS:
            continue
        up = by_seq.get(s.parent)
        while up is not None and up.seq not in ticks:
            up = by_seq.get(up.parent)
        if up is not None:
            waits[up.seq] += s.t1 - s.t0
    host = [t.t1 - t.t0 - waits[k] for k, t in ticks.items()]
    return 1e3 * sum(host) / len(host)
