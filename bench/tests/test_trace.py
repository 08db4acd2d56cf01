"""The trace reduction, on hand-made intervals and on a small trace
recorded once from a CPU run (``data/cpu_window.xplane.pb``: three calls
of a jitted ``tanh(x @ x).sum()``, each inside a ``bench.submit`` span, in
one ``bench.window`` span)."""
from pathlib import Path

import pytest

from harness import trace as T

DATA = Path(__file__).parent / "data" / "cpu_window.xplane.pb"


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
           ev("all-gather-start", 3, 3.5)]
    assert T.collective_seconds(ops) == pytest.approx(1.5)
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


def test_programs_told_apart_by_what_ran_inside():
    from harness import programs
    mods = [ev("jit__unknown(1)", 0, 10), ev("jit__lambda(2)", 10, 14),
            ev("jit__lambda(3)", 14, 15), ev("jit__argmax(4)", 15, 16)]
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
    assert programs.stages(mods, ops)["tick"] == [mods[0]]
    assert [e.name[:9] for e in programs.leaves(ops)] == [
        "%paged_de", "%fusion.2", "%paged_ch", "%fusion.1", "%reduce.1"]
    assert programs.short(ops[2].name) == "fusion.2 bf16[32,18944]"
    assert len(programs.kernel_ops(ops, programs.DECODE_KERNEL)) == 1
