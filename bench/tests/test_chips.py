"""The per-layer readers over every chip of a cell.

A hand-made window of the fleet cell: a few control steps, two fused
ticks, two prefill chunks and a vision run, each program with the kernel
or the vision weights it runs, laid on the device lines of one or of four
chips. On four chips that split the work evenly, each program lasting a
quarter as long and the whole window a quarter as long, every share reads
what one chip reads; chips of unequal speed read the sum of their times.
A chip whose trace stops short of the others' gives no device reading.
The readings of one chip, and those of the trace recorded from a CPU run
(``data/cpu_window.xplane.pb``), are pinned at what the readers gave
before they counted more than one chip. The CPU trace holds no program of
the served path, so its device-stage readers read None there: only the
hand-made window pins them."""
import dataclasses
from pathlib import Path
from types import SimpleNamespace

import pytest

import run as R
from harness import common
from harness import spec
from harness import trace as T
from repro.serving.tracing import Span, SpanRecorder

DATA = Path(__file__).parent / "data" / "cpu_window.xplane.pb"
CONFIG = spec.config("molmoact-7b-16L")
MIX = spec.traffic("robots32")
PEAKS = spec.peaks("TPU v5 lite")
PROMPT = 640
ENGINE = dict(slots=32, page_size=32, chunk_size=128, max_seq=1024,
              tick_tokens=16, prompt=PROMPT)
NEW = MIX["cot_tokens"] + MIX["action_tokens"]
COUNTERS = dict(tokens_decoded=1700, device_steps=64, prefill_tokens=1280,
                ticks=2)
READERS = [m["name"] for m in spec.benchmark()["per_layer"]]
SHARES = ("decode_hbm_pct", "decode_attn_roofline", "chunk_attn_roofline",
          "mfu_pct")

# (module, start, end, [(op, start, end)]) on a 10 s window
PROGRAMS = [
    ("jit_serve_tick(101)", 0.5, 2.5, [
        ("%while.1 = (s32[]) while(...)", 0.5, 2.4),
        ("%paged_decode_attention.8 = bf16[32,4,7,128]{3} custom-call(...)",
         0.6, 0.7),
        ("%fusion.2 = bf16[32,18944]{1,0} fusion(...)", 0.7, 2.3)]),
    ("jit_serve_chunk(202)", 3.0, 3.4, [
        ("%paged_chunk_prefill_attention.8 = bf16[1,28,128,128]{3} "
         "custom-call(...)", 3.05, 3.1),
        ("%fusion.159 = bf16[128,18944]{1,0} fusion(...)", 3.1, 3.35)]),
    ("jit_serve_vision(303)", 4.0, 4.05, [
        ("%fusion.105 = bf16[1,576,1024]{2} fusion(%p__vision____pos__.1)",
         4.0, 4.05)]),
    ("jit_convert_element_type(404)", 4.5, 4.52, [
        ("%convert.1 = bf16[1,640,3584]{2,1,0} convert(...)", 4.5, 4.52)]),
    ("jit_serve_chunk(202)", 5.0, 5.4, [
        ("%paged_chunk_prefill_attention.8 = bf16[1,28,128,128]{3} "
         "custom-call(...)", 5.05, 5.12),
        ("%fusion.159 = bf16[128,18944]{1,0} fusion(...)", 5.12, 5.35)]),
    ("jit_serve_tick(101)", 6.0, 8.5, [
        ("%while.1 = (s32[]) while(...)", 6.0, 8.4),
        ("%paged_decode_attention.8 = bf16[32,4,7,128]{3} custom-call(...)",
         6.1, 6.25),
        ("%fusion.2 = bf16[32,18944]{1,0} fusion(...)", 6.25, 8.3)]),
]


def fleet(t0, t1, scale=1.0):
    """Six control steps, two of them prefilled inside [t0, t1], all of
    them decoding tokens inside it and finishing before its close."""
    steps = []
    for k in range(6):
        t_submit = t0 + scale * (-1.0 + 0.8 * k)
        t_first = t_submit + scale * 0.9
        times = [t_first + scale * 0.004 * j for j in range(NEW)]
        req = SimpleNamespace(t_submit=t_submit + scale * 0.002,
                              queue_s=scale * 0.05 * (k + 1), trace=None)
        steps.append(SimpleNamespace(t_submit=t_submit, t_first=t_first,
                                     t_done=times[-1], token_times=times,
                                     request=req))
    assert all(s.t_done <= t1 for s in steps)
    return SimpleNamespace(steps=steps, counted=lambda: [
        s for s in steps if t0 < s.t_done <= t1])


def recorder(t0, scale=1.0):
    rec = SpanRecorder(maxlen=64)
    for seq, name, a, b, parent in [
            (1, "engine.tick", 0.4, 2.6, -1),
            (2, "engine.readback", 2.4, 2.6, 1),
            (3, "frontend.drain", 2.6, 2.7, -1),
            (4, "engine.tick", 2.9, 5.6, -1),
            (5, "engine.chunk", 3.0, 3.1, 4),
            (6, "host.gc", 5.7, 5.71, -1),
            (7, "engine.tick", 5.9, 8.6, -1),
            (8, "engine.readback", 8.4, 8.6, 7)]:
        rec.add(Span(name, t0 + scale * a, t0 + scale * b, parent, {}, seq))
    return rec


def make_run(factors=(1.0,), scale=1.0, starts=None, stops=None):
    """The window on ``len(factors)`` chips: chip i's programs last
    ``factors[i]`` of their length above, and every time is ``scale`` of
    its value above. Chip i's trace holds only the programs that start at
    or after ``starts[i]`` and before ``stops[i]`` (times above)."""
    t0, t1 = 100.0, 100.0 + scale * 10.0
    n = len(factors)
    starts, stops = starts or [0.0] * n, stops or [10.0] * n
    tr = T.Trace()
    for d, f in enumerate(factors):
        mods, ops = [], []
        for name, a, b, inner in PROGRAMS:
            if not starts[d] <= a < stops[d]:
                continue
            a0 = t0 + scale * a
            mods.append(T.Event(name, a0, a0 + scale * f * (b - a)))
            ops += [T.Event(n, a0 + scale * f * (x - a),
                            a0 + scale * f * (y - a)) for n, x, y in inner]
        tr.modules[d] = mods
        tr.ops[d] = sorted(ops, key=lambda e: (e.start, -e.end))
    return _run(tr, (t0, t1), fleet(t0, t1, scale), recorder(t0, scale),
                len(factors))


def _run(trace, span, fl, rec, chips, config=CONFIG):
    for s in fl.steps:
        s.request.trace = rec
    w = R.Window(fl, span[0], span[1], dict(COUNTERS), 0, 0, trace, span)
    return R.Run(config, MIX, ENGINE, w, PEAKS, chips)


def recorded_run():
    """The CPU trace as one chip's window, the same fleet in it. The window
    lasts milliseconds, so the steps are of the registry's reduced model,
    which one chip can run that fast."""
    from repro.configs import get_config
    tr = T.load(str(DATA))
    win = next(e for e in tr.host if e.name == "bench.window")
    a, b = win.start, win.end
    scale = (b - a) / 10.0
    return _run(tr, (a, b), fleet(a, b, scale), recorder(a, scale), 1,
                R.sizes_of(get_config("molmoact-7b").reduced()))


def readings(run):
    return {name: spec.metric_reader(name)(run) for name in READERS}


# the readers that read the device's lines of the trace
DEVICE = ("vision_ms", "prefill_chunk_ms", "decode_step_ms",
          "decode_attn_roofline", "chunk_attn_roofline", "decode_hbm_pct",
          "idle_pct", "idle_unspanned_pct")

# what every reader gave on these windows before it counted more than one
# chip (the readers of the parent of this change, run on the same windows)
ONE_CHIP = {
    "admit_wait_p95_s": 0.2894999999999932,
    "first_token_p95_s": 0.9000000000000057,
    "decode_rows_mean": 26.5625,
    "vision_ms": 49.99999999999716,
    "prefill_chunk_ms": 400.0000000000057,
    "decode_step_ms": 70.3125,
    "decode_share": 45.91346153846125,
    "decode_attn_roofline": 13.515491680585313,
    "chunk_attn_roofline": 1.0669271469271062,
    "decode_hbm_pct": 15.62830439289106,
    "mfu_pct": 2.0928962718018274,
    "idle_pct": 50.30000000000001,
    "tick_host_ms": 2399.9999999999964,
    "loop_gap_ms": 300.00000000001137,
    "gc_ms_per_s": 0.9999999999990905,
    "idle_unspanned_pct": 22.900000000000347,
}
RECORDED = {
    "admit_wait_p95_s": 0.0002018995870500001,
    "first_token_p95_s": 0.0006276671100000001,
    "decode_rows_mean": 26.5625,
    "vision_ms": None,
    "prefill_chunk_ms": None,
    "decode_step_ms": None,
    "decode_share": 45.91346153846154,
    "decode_attn_roofline": None,
    "chunk_attn_roofline": None,
    "decode_hbm_pct": None,
    "mfu_pct": 0.2469882578524025,
    "idle_pct": 95.77689039656707,
    "tick_host_ms": 1.67377896,
    "loop_gap_ms": 0.20922237000000016,
    "gc_ms_per_s": 0.9999999999999981,
    "idle_unspanned_pct": 21.11223705667802,
}


def test_one_chip_reads_as_before():
    assert readings(make_run()) == ONE_CHIP


def test_recorded_cpu_trace_reads_as_before():
    assert readings(recorded_run()) == RECORDED


def test_even_split_over_four_chips_reads_as_one():
    one = readings(make_run())
    four = readings(make_run((1.0,) * 4, scale=0.25))
    for name in SHARES:
        assert four[name] == pytest.approx(one[name], rel=1e-9), name
        assert 0 < four[name] <= 100
    # each chip's device time is a quarter of the one chip's
    for name in ("decode_step_ms", "prefill_chunk_ms", "vision_ms"):
        assert four[name] == pytest.approx(one[name] / 4, rel=1e-9), name


def test_unequal_chips_read_the_sum_of_their_times():
    one = readings(make_run())
    uneven = readings(make_run((0.1, 0.2, 0.3, 0.4)))
    for name in SHARES[:3]:
        assert uneven[name] == pytest.approx(one[name], rel=1e-9), name
    assert uneven["decode_step_ms"] == pytest.approx(
        one["decode_step_ms"] / 4, rel=1e-9)
    slow = readings(make_run((0.25, 0.25, 0.25, 1.0)))
    assert slow["decode_hbm_pct"] == pytest.approx(
        one["decode_hbm_pct"] / 1.75, rel=1e-9)
    assert slow["decode_step_ms"] == pytest.approx(
        one["decode_step_ms"] * 1.75 / 4, rel=1e-9)


def test_a_chip_cut_short_gives_no_device_reading():
    whole = readings(make_run((1.0,) * 4, scale=0.25))
    for cut in (dict(stops=[5.0, 10.0, 10.0, 10.0]),
                dict(stops=[10.0, 10.0, 10.0, 4.6]),
                dict(starts=[0.0, 4.0, 0.0, 0.0])):
        run = make_run((1.0,) * 4, scale=0.25, **cut)
        assert common.chips(run) == [], cut
        got = readings(run)
        for name in READERS:
            assert got[name] == (None if name in DEVICE else whole[name]), \
                (cut, name)


def test_chips_that_cover_the_same_window_are_read():
    assert common.chips(make_run((1.0,) * 4, scale=0.25)) == [0, 1, 2, 3]
    # a slow chip ends its last program later, by less than that program
    assert common.chips(make_run((0.25, 0.25, 0.25, 1.0))) == [0, 1, 2, 3]
    # one chip is compared with none
    assert common.chips(make_run(stops=[5.0])) == [0]
    # a trace of more devices than the cell uses: the first of them, and a
    # device beyond them that stops short is none of the cell's
    run = make_run((1.0,) * 4, scale=0.25, stops=[10.0] * 3 + [5.0])
    assert common.chips(dataclasses.replace(run, chips=3)) == [0, 1, 2]
