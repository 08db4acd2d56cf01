"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-name device time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes. On a TPU
each chip is a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line holds the
operations it ran and whose ``XLA Modules`` line holds the programs (one
event per execution of a jitted program). On the CPU the operations run on
the client's thread-pool lines of ``/host:CPU``; they stand in for one
device, which is what the committed test trace exercises. Host spans (the
benchmark's ``jax.profiler.TraceAnnotation``s, named ``bench.*``) come
from whichever host line holds them. Times are seconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

# a collective by its own opcode where the event gives the op's text
# (``%psum.24 = bf16[...] all-reduce(%fusion.204)``: the instruction's name
# may come from any JAX primitive, and a fusion fed by a psum names it among
# its operands), by its own name where the event gives a name alone
COLLECTIVE_OP = re.compile(
    r"^%?[\w.-]+ = (?:\(.*?\)|\S+) (all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(-start|-done)?\(")
COLLECTIVE_NAME = re.compile(
    r"^%?(all[-_]reduce|all[-_]gather|reduce[-_]scatter|"
    r"collective[-_]permute|all[-_]to[-_]all|psum|ppermute)", re.I)


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[dev] = _events(line)
                elif line.name == "XLA Modules":
                    tr.modules[dev] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = _events(line)
                tr.host += [e for e in evs if e.name.startswith("bench.")]
                if line.name.startswith(("tf_XLAPjRtCpuClient",
                                           "tf_XLAEigen")):
                    cpu_ops += [e for e in evs if e.dur > 0
                                and not e.name.startswith("Threadpool")]
    if not tr.ops and cpu_ops:
        tr.ops[0] = cpu_ops
    for evs in list(tr.ops.values()) + list(tr.modules.values()):
        evs.sort(key=lambda e: (e.start, -e.end))   # a container first
    return tr


def clip(events: List[Event], t0: float, t1: float) -> List[Event]:
    """The parts of ``events`` inside [t0, t1]."""
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Disjoint covering intervals of ``events``, in time order."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy(events: List[Event], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which some event runs."""
    return sum(b - a for a, b in union(clip(events, t0, t1)))


def gaps(events: List[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [t0, t1], longest first."""
    out, cur = [], t0
    for a, b in union(clip(events, t0, t1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        out.append((cur, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def by_name(events: List[Event]) -> Dict[str, float]:
    """Total seconds per event name."""
    out: Dict[str, float] = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def is_collective(name: str) -> bool:
    if " = " in name:
        return bool(COLLECTIVE_OP.match(name))
    return bool(COLLECTIVE_NAME.match(name))


def collective_seconds(events: List[Event]) -> float:
    return sum(e.dur for e in events if is_collective(e.name))


def host_span_at(host: List[Event], t: float, prefix: str = "bench."
                 ) -> str:
    """Name of the innermost benchmark host span covering time ``t``."""
    best = None
    for e in host:
        if e.name.startswith(prefix) and e.start <= t < e.end:
            if best is None or e.dur < best.dur:
                best = e
    return best.name if best is not None else "no benchmark span"
