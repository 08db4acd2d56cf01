"""The serving stack's spans (``serving.tracing``): each request's spans
carry its uid in causal order through ``AsyncFrontend``, every engine span
sits under its tick, the ring is bounded, collections and compiles are
spans, and a profiler trace shows the same spans on its own clock."""
from __future__ import annotations

import asyncio
import gc
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import AsyncFrontend, ServingEngine
from repro.serving.tracing import Span, SpanRecorder, span

from conftest import reduced_params


def _serve(opts, n_req=3, prompt_len=12):
    """Requests with a vision prefix through a paged chunked engine
    (chunks of 8 over 8 patches + ``prompt_len`` tokens), behind the front
    end with ticks on its worker thread. Returns the finished requests."""
    cfg, params = reduced_params("molmoact-7b")
    eng = ServingEngine(cfg, opts, params, n_slots=2, max_seq=64, eos=-1,
                        tick_tokens=4, paged=True, page_size=8,
                        chunked_prefill=True, chunk_size=8, token_budget=16)
    rng = np.random.default_rng(3)
    patches = [rng.standard_normal((cfg.vision.num_tokens,
                                    cfg.vision.embed_dim)).astype(np.float32)
               for _ in range(n_req)]

    async def go():
        async with AsyncFrontend([eng]) as fe:
            streams = [await fe.submit(
                rng.integers(0, cfg.vocab_size, prompt_len, dtype=np.int32),
                6, patches=p) for p in patches]
            for s in streams:
                await s.tokens()
            return [s.request for s in streams]

    return asyncio.run(go()), cfg


def test_request_spans_share_uid_in_causal_order(opts):
    reqs, cfg = _serve(opts)
    total = cfg.vision.num_tokens + 12
    for req in reqs:
        assert req.trace is not None
        mine = sorted((s for s in req.trace.spans()
                       if s.ids.get("uid") == req.uid), key=lambda s: s.t0)
        names = [s.name for s in mine]
        n_chunks = len(names) - 5
        assert n_chunks >= -(-total // 8)
        assert names == (["frontend.submit", "engine.admit", "engine.vision",
                          "engine.embed"] + ["engine.chunk"] * n_chunks
                         + ["engine.first_token"]), names
        submit, admit, vision, embed = mine[:4]
        chunks, first = mine[4:-1], mine[-1]
        assert submit.t1 <= admit.t0
        assert admit.t0 <= vision.t0 <= vision.t1 <= embed.t0 <= admit.t1
        assert vision.parent == admit.seq == embed.parent
        starts = [c.ids["start"] for c in chunks]
        assert starts[0] == 0 and starts == sorted(set(starts))
        assert starts[-1] + 8 >= total
        assert all(a.t1 <= b.t0 for a, b in zip([admit] + chunks, chunks))
        assert first.parent == chunks[-1].seq
        assert chunks[-1].t0 <= first.t0 <= first.t1 <= chunks[-1].t1
        assert req.t_prefill >= first.t1


def test_every_engine_span_is_under_a_tick(opts):
    reqs, _ = _serve(opts, n_req=2)
    rec = reqs[0].trace
    ring = rec.spans()
    by_seq = {s.seq: s for s in ring}
    engine = [s for s in ring if s.name.startswith("engine.")
              and s.name != "engine.tick"]
    assert {"engine.admit", "engine.plan", "engine.chunk", "engine.decode",
            "engine.readback", "engine.emit"} <= {s.name for s in engine}
    for s in engine:
        up = by_seq[s.parent]
        while up.name != "engine.tick":
            up = by_seq[up.parent]
        assert up.t0 <= s.t0 <= s.t1 <= up.t1
    assert rec.counts["engine.tick"] == sum(
        s.name == "engine.tick" for s in ring)
    for name in ("frontend.drain", "frontend.pump", "frontend.submit"):
        assert rec.counts[name] > 0


def test_phase_report_reads_the_spans(opts):
    reqs, _ = _serve(opts, n_req=2)
    rec = reqs[0].trace
    from repro.serving.engine import EngineStats
    st = EngineStats(spans=rec)
    rep = st.phase_report()
    tot = rec.totals
    assert tot["engine.vision"] > 0
    assert rep["vision"] == pytest.approx(tot["engine.vision"])
    assert rep["prefill"] == pytest.approx(
        tot["engine.admit"] - tot["engine.vision"] + tot["engine.chunk"])
    assert rep["decode"] == pytest.approx(
        tot["engine.decode"] + tot["engine.readback"])
    ticks = st.decode_tick_s()
    assert len(ticks) == rec.counts["engine.readback"]
    assert rep["decode_tick_p99"] >= rep["decode_tick_p50"] > 0


def test_full_ring_drops_the_oldest_and_counts_them():
    was = gc.isenabled()
    gc.disable()                 # no host.gc span may land in the ring
    try:
        rec = SpanRecorder(maxlen=4)
        for i in range(6):
            with span("x", rec, i=i):
                pass
    finally:
        if was:
            gc.enable()
    ring = rec.spans()
    assert [s.ids["i"] for s in ring] == [2, 3, 4, 5]
    assert rec.dropped == 2 and rec.counts["x"] == 6
    assert rec.dropped_t1 < ring[0].t0
    assert rec.intact_since(ring[0].t0)
    assert not rec.intact_since(rec.dropped_t1)
    assert len(rec.ring) == rec.ring.maxlen == 4


def test_threads_lose_no_span():
    """Spans closed on many threads at once (the front end's loop, the
    tick worker, collections) are all counted and totalled."""
    rec = SpanRecorder(maxlen=1000)
    n_threads, per = 2 * (os.cpu_count() or 2), 500
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=lambda: [
            rec.add(Span("x", 0.0, 1.0, -1, {}, -1)) for _ in range(per)])
            for _ in range(n_threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(w.is_alive() for w in workers)
    n = n_threads * per
    assert rec.counts["x"] == n and rec.totals["x"] == float(n)
    assert rec.dropped == sum(rec.counts.values()) - len(rec.ring) > 0


def test_forced_collection_is_a_host_gc_span():
    rec = SpanRecorder()
    with span("outer", rec) as outer:
        gc.collect()
    got = [s for s in rec.spans() if s.name == "host.gc"
           and s.parent == outer._seq]
    assert got and got[-1].ids == {"generation": 2}
    (o,) = rec.spans(name="outer")
    assert o.t0 <= got[-1].t0 <= got[-1].t1 <= o.t1
    assert rec.totals["host.gc"] > 0


def test_backend_compile_is_a_jax_compile_span():
    rec = SpanRecorder()

    def seven_fold(x):
        return 7.0 * x + 1.0
    with span("engine.decode", rec) as sp:
        jax.jit(seven_fold)(jnp.arange(5.0)).block_until_ready()
    got = [s for s in rec.spans(name="jax.compile") if s.parent == sp._seq]
    assert got and "seven_fold" in got[-1].ids["fun"]
    (d,) = rec.spans(name="engine.decode")
    assert d.t0 <= got[-1].t0 <= got[-1].t1 <= d.t1


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return out


def test_profiler_trace_shows_the_spans_on_the_host_clock(tmp_path):
    rec = SpanRecorder()
    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    with span("engine.tick", rec):
        with span("engine.readback", rec, uid=4):
            (x @ x).block_until_ready()
        time.sleep(0.002)
    t_close = time.perf_counter()
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    assert len(ev["bench.window"]) == 1
    shift = ev["bench.window"][0][1] - t_close
    for name in ("engine.tick", "engine.readback"):
        (mine,) = rec.spans(name=name)
        (got,) = ev[name]
        assert abs(mine.t0 + shift - got[0]) < 5e-4, name
        assert abs(mine.t1 + shift - got[1]) < 5e-4, name
