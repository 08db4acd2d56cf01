"""Which device events belong to which stage of the served path.

On the TPU each execution of a jitted program is an event of the device's
``XLA Modules`` line, and the operations it ran are events of ``XLA Ops``
inside it (a ``while`` op holds the ops of its body). The engine's
programs carry no names of their own yet (the tick is ``jit__unknown``,
vision and chunk are both ``jit__lambda``), so a program execution is
told apart by what ran inside it:

- ``tick``: it ran the paged decode-attention kernel;
- ``chunk``: it ran the paged chunk-prefill kernel;
- ``vision``: it read the vision tower's weights (XLA names a jitted
  argument's leaves after their path, ``p__vision__...``).

Pallas kernels appear as custom calls named after their wrappers
(``paged_decode_attention``, ``paged_chunk_prefill_attention``).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

from harness.trace import Event

DECODE_KERNEL = re.compile(r"^%paged_decode_attention[.\s]")
CHUNK_KERNEL = re.compile(r"^%paged_chunk_prefill_attention[.\s]")
VISION_WEIGHTS = re.compile(r"p__vision__")
STAGES = ("tick", "chunk", "vision")


def short(op_name: str) -> str:
    """``%fusion.180 = bf16[32,18944]{...} fusion(...)`` ->
    ``fusion.180 bf16[32,18944]``."""
    head, _, rest = op_name.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{')[0]}".strip()


def assign(modules: List[Event], ops: List[Event]
           ) -> List[Tuple[Event, str, List[Event]]]:
    """Each program execution with its stage and the ops it ran. A module
    of none of the three stages keeps its own name (up to the fingerprint)
    as its stage."""
    starts = [e.start for e in ops]
    out = []
    for m in modules:
        lo = bisect.bisect_left(starts, m.start)
        hi = bisect.bisect_left(starts, m.end)
        inner = ops[lo:hi]
        if any(DECODE_KERNEL.search(e.name) for e in inner):
            stage = "tick"
        elif any(CHUNK_KERNEL.search(e.name) for e in inner):
            stage = "chunk"
        elif any(VISION_WEIGHTS.search(e.name) for e in inner):
            stage = "vision"
        else:
            stage = m.name.split("(")[0]
        out.append((m, stage, inner))
    return out


def stages(modules: List[Event], ops: List[Event]) -> Dict[str, List[Event]]:
    """Program executions by stage: ``tick``, ``chunk``, ``vision``."""
    out: Dict[str, List[Event]] = {s: [] for s in STAGES}
    for m, stage, _ in assign(modules, ops):
        if stage in out:
            out[stage].append(m)
    return out


def kernel_ops(ops: List[Event], pattern) -> List[Event]:
    return [e for e in ops if pattern.search(e.name)]


def leaves(ops: List[Event]) -> List[Event]:
    """The ops that hold no other op (a ``while`` holds its body's)."""
    return [e for i, e in enumerate(ops)
            if i + 1 >= len(ops) or ops[i + 1].start >= e.end]
