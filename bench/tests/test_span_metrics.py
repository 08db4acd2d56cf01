"""The four readers of the program's spans (``tick_host_ms``,
``loop_gap_ms``, ``gc_ms_per_s``, ``idle_unspanned_pct``) on a hand-made
run: a recorder holding known spans and a trace holding known device ops,
with every expected value worked out by hand below."""
from types import SimpleNamespace

import pytest

import run as R
from harness import spec
from harness import trace as T
from repro.serving.tracing import Span, SpanRecorder

# (seq, name, t0, t1, parent) on the host clock; the window is [10, 20]
SPANS = [
    (3, "engine.admit", 11.00, 11.10, 1),
    (4, "engine.vision", 11.02, 11.08, 3),
    (2, "engine.readback", 11.20, 11.40, 1),
    (1, "engine.tick", 11.00, 11.50, -1),     # host 0.5 - 0.2 - 0.06 = 0.24
    (5, "frontend.drain", 11.50, 11.60, -1),
    (8, "engine.first_token", 11.80, 11.85, 7),
    (7, "engine.chunk", 11.70, 11.90, 6),
    (6, "engine.tick", 11.70, 12.00, -1),     # host 0.3 - 0.05 = 0.25
    (9, "frontend.park", 12.00, 13.00, -1),
    (10, "frontend.drain", 13.00, 13.10, -1),
    (12, "engine.readback", 13.20, 13.40, 11),
    (11, "engine.tick", 13.10, 13.50, -1),    # host 0.4 - 0.2 = 0.2
    (13, "host.gc", 13.60, 13.62, -1),
    (14, "host.gc", 9.00, 9.50, -1),          # before the window
    (15, "engine.tick", 19.50, 20.50, -1),    # not wholly inside it
]
# device ops on the trace's clock, which runs 100 s ahead: the window is
# [110, 120] there, and its gaps are [111.0, 111.25), [111.35, 113.2) and
# [113.4, 115.0): 3.7 s idle
OPS = [(110.0, 111.0), (111.25, 111.35), (113.2, 113.4), (115.0, 120.0)]


def make_run(spans=SPANS, maxlen=64, request=None, traced=True):
    rec = SpanRecorder(maxlen=maxlen)
    for seq, name, t0, t1, parent in spans:
        rec.add(Span(name, t0, t1, parent, {}, seq))
    req = request if request is not None else SimpleNamespace(trace=rec)
    step = SimpleNamespace(request=req)
    fleet = SimpleNamespace(counted=lambda: [step])
    w = R.Window(fleet, 10.0, 20.0, {}, 0, 0)
    if traced:
        w.trace = T.Trace(ops={0: [T.Event("op", a, b) for a, b in OPS]})
        w.trace_span = (110.0, 120.0)
    return R.Run({}, {}, {}, w, {}, 1)


def read(name, run):
    return spec.metric_reader(name)(run)


def test_tick_host_ms_by_hand():
    # three whole ticks in the window: (0.24 + 0.25 + 0.2) / 3 s
    assert read("tick_host_ms", make_run()) == pytest.approx(230.0)


def test_loop_gap_ms_by_hand():
    # 11.5 -> 11.7 counts; 12.0 -> 13.1 holds a park and does not
    assert read("loop_gap_ms", make_run()) == pytest.approx(200.0)


def test_gc_ms_per_s_by_hand():
    # 0.02 s of the window's collections over its 10 s
    assert read("gc_ms_per_s", make_run()) == pytest.approx(2.0)


def test_idle_unspanned_pct_by_hand():
    # named on the trace's clock: [111.0, 111.6], [111.7, 113.5],
    # [113.6, 113.62], [119.5, ...]; the idle left unnamed is
    # [111.6, 111.7) + [113.5, 113.6) + [113.62, 115.0) = 1.58 s of 10
    run = make_run()
    got = read("idle_unspanned_pct", run)
    assert got == pytest.approx(15.8)
    assert 0 <= got <= read("idle_pct", run) == pytest.approx(37.0)


def test_readers_stay_silent_without_the_program_spans():
    runs = [make_run(request=SimpleNamespace()),      # a program without
            make_run(maxlen=4)]                       # a ring that dropped
    for run in runs:
        for name in ("tick_host_ms", "loop_gap_ms", "gc_ms_per_s",
                     "idle_unspanned_pct"):
            assert read(name, run) is None, name
    assert read("idle_unspanned_pct", make_run(traced=False)) is None
    assert read("tick_host_ms", make_run(traced=False)) == pytest.approx(230)
