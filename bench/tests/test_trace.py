"""The trace reduction, on hand-made intervals, on a small trace
recorded once from a CPU run (``data/cpu_window.xplane.pb``: three calls
of a jitted ``tanh(x @ x).sum()``, each inside a ``bench.submit`` span, in
one ``bench.window`` span), and on the program executions of traced chip
windows (``data/chip_modules.json.gz``: each chip's ``XLA Modules``
events inside the window, one chip of the two cells and all four of a
``model=4`` mesh, with the stage that the reduction gave each when it
still told the stages apart by the kernels and vision weights that ran
inside them)."""
import gzip
import json
from pathlib import Path

import pytest

from harness import trace as T

DATA = Path(__file__).parent / "data" / "cpu_window.xplane.pb"
CHIP = Path(__file__).parent / "data" / "chip_modules.json.gz"


def ev(name, a, b):
    return T.Event(name, a, b)


def test_union_busy_and_gaps_by_hand():
    evs = [ev("a", 1.0, 2.0), ev("b", 1.5, 3.0), ev("c", 5.0, 6.0),
           ev("d", 9.0, 12.0)]
    assert T.union(evs) == [(1.0, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert T.busy(evs, 0.0, 10.0) == pytest.approx(2.0 + 1.0 + 1.0)
    assert T.gaps(evs, 0.0, 10.0) == [(6.0, 9.0), (3.0, 5.0), (0.0, 1.0)]
    assert T.by_name(evs + [ev("a", 7.0, 7.5)]) == pytest.approx(
        {"a": 1.5, "b": 1.5, "c": 1.0, "d": 3.0})


def test_collectives_and_host_spans_by_hand():
    ops = [ev("all-reduce.3", 0, 1), ev("fusion.1", 1, 3),
           ev("all-gather-start", 3, 3.5),
           ev("%psum.24 = bf16[32,1,3584]{2,0,1} all-reduce(%fusion.204)",
              4, 4.25),
           ev("%fusion.210 = bf16[32,1,3584]{2,0,1} fusion(%psum.24)", 5, 7),
           # instructions named after the JAX primitive, not the opcode
           ev("%all_gather.3 = bf16[32,152064]{1,0:T(8,128)(2,1)} "
              "all-gather(%dot.7), dimensions={1}", 7, 7.5),
           ev("%reduce_scatter.1 = (bf16[8]{0:T(256)}, bf16[8]{0:T(256)}) "
              "reduce-scatter-start(%a, %b)", 7.5, 7.75),
           ev("%ppermute.2 = bf16[4]{0} collective-permute(%c)", 8, 8.125),
           ev("ppermute.5", 9, 9.125),
           ev("%copy.1 = bf16[4]{0} copy(%all_gather.3)", 10, 12)]
    assert T.collective_seconds(ops) == pytest.approx(2.75)
    host = [ev("bench.window", 0, 10), ev("bench.submit", 2, 3),
            ev("other", 0, 10)]
    assert T.host_span_at(host, 2.5) == "bench.submit"
    assert T.host_span_at(host, 5.0) == "bench.window"
    assert T.host_span_at(host, 11.0) == "no benchmark span"


def test_recorded_cpu_trace():
    tr = T.load(str(DATA))
    assert list(tr.ops) == [0]
    ops = tr.ops[0]
    names = T.by_name(ops)
    assert sum(e.name == "dot_general.1" for e in ops) == 3
    win = [e for e in tr.host if e.name == "bench.window"]
    subs = [e for e in tr.host if e.name == "bench.submit"]
    assert len(win) == 1 and len(subs) == 3
    a, b = win[0].start, win[0].end
    busy = T.busy(ops, a, b)
    idle = sum(g1 - g0 for g0, g1 in T.gaps(ops, a, b))
    assert 0 < busy < b - a
    assert busy + idle == pytest.approx(b - a)
    # every op ran inside a submit span, and the longest gap lies between
    # two of them (the host slept there)
    for e in ops:
        assert T.host_span_at(tr.host, e.start) == "bench.submit"
    g0, g1 = T.gaps(ops, a, b)[0]
    assert T.host_span_at(tr.host, (g0 + g1) / 2) == "bench.window"
    assert names["dot_general.1"] == pytest.approx(
        sum(e.dur for e in ops if e.name == "dot_general.1"))


def test_programs_told_apart_by_name():
    # the stage programs by their own names; the ops that ran inside each
    # are its own, and a program of no stage keeps its name
    from harness import programs
    mods = [ev("jit_serve_tick(1)", 0, 10), ev("jit_serve_chunk(2)", 10, 14),
            ev("jit_serve_vision(3)", 14, 15), ev("jit__argmax(4)", 15, 16)]
    ops = [ev("%while.1 = (s32[]) while(...)", 0, 9),
           ev("%paged_decode_attention.8 = bf16[32,4,7,128]{3} "
              "custom-call(...)", 1, 3),
           ev("%fusion.2 = bf16[32,18944]{1,0} fusion(...)", 3, 9),
           ev("%paged_chunk_prefill_attention.8 = bf16[1,28,128,128]{3} "
              "custom-call(...)", 10, 12),
           ev("%fusion.105 = bf16[1,576,1024]{2} fusion(%p__vision____pos__"
              ".1)", 14, 15),
           ev("%reduce.1 = s32[1]{0} reduce(...)", 15, 16)]
    ran = programs.assign(mods, ops)
    assert [s for _, s, _ in ran] == ["tick", "chunk", "vision",
                                      "jit__argmax"]
    assert [len(inner) for _, _, inner in ran] == [3, 1, 1, 1]
    assert programs.stages(mods)["tick"] == [mods[0]]
    assert [e.name[:9] for e in programs.leaves(ops)] == [
        "%paged_de", "%fusion.2", "%paged_ch", "%fusion.1", "%reduce.1"]
    assert programs.short(ops[2].name) == "fusion.2 bf16[32,18944]"
    assert len(programs.kernel_ops(ops, programs.DECODE_KERNEL)) == 1
    # the name decides, not what ran inside: a spec tick runs the chunk
    # kernel and is no chunk, and a tick cut short by the window is a tick
    assert programs.stage_of(ev("jit_serve_spec_tick(5)", 0, 1)) == \
        "jit_serve_spec_tick"
    assert programs.stages([ev("jit_serve_tick(1)", 0, 1)])["tick"]


def test_recorded_chip_windows_get_the_same_stages():
    from harness import programs
    with gzip.open(CHIP, "rt") as f:
        cells = json.load(f)["cells"]
    for cell, chips in cells.items():
        counts = []
        for chip, rows in chips.items():
            mods = [ev(name, a, b) for name, a, b, _ in rows]
            got = [stage for _, stage, _ in programs.assign(mods, [])]
            # on a mesh the vision weights' ops carry other names, and the
            # old rule left the vision program its module's name
            want = ["vision" if stage == "jit_serve_vision" else stage
                    for *_, stage in rows]
            assert got == want, (cell, chip)
            counts.append({s: len(m) for s, m in
                           programs.stages(mods).items()})
            assert all(counts[-1].values()), (cell, chip, counts[-1])
        # on a mesh every chip runs every program
        assert all(c == counts[0] for c in counts), (cell, counts)
