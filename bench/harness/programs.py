"""Which device events belong to which stage of the served path.

On the TPU each execution of a jitted program is an event of the device's
``XLA Modules`` line, named after the program and its fingerprint
(``jit_serve_tick(1234...)``), and the operations it ran are events of
``XLA Ops`` inside it (a ``while`` op holds the ops of its body). The
engine's stage programs carry names of their own, on one chip and on a
mesh alike, and a stage is matched by that name:

- ``tick``: ``jit_serve_tick``, the fused decode tick;
- ``chunk``: ``jit_serve_chunk``, one chunk of chunked prefill;
- ``vision``: ``jit_serve_vision``, the vision tower.

Any other program keeps its own name (up to the fingerprint) as its stage.
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
PROGRAMS = {"jit_serve_tick": "tick", "jit_serve_chunk": "chunk",
            "jit_serve_vision": "vision"}
STAGES = tuple(PROGRAMS.values())


def short(op_name: str) -> str:
    """``%fusion.180 = bf16[32,18944]{...} fusion(...)`` ->
    ``fusion.180 bf16[32,18944]``."""
    head, _, rest = op_name.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{')[0]}".strip()


def stage_of(module: Event) -> str:
    """The stage a program execution belongs to, by the program's name."""
    name = module.name.split("(")[0]
    return PROGRAMS.get(name, name)


def assign(modules: List[Event], ops: List[Event]
           ) -> List[Tuple[Event, str, List[Event]]]:
    """Each program execution with its stage and the ops it ran."""
    starts = [e.start for e in ops]
    return [(m, stage_of(m), ops[bisect.bisect_left(starts, m.start):
                                  bisect.bisect_left(starts, m.end)])
            for m in modules]


def stages(modules: List[Event]) -> Dict[str, List[Event]]:
    """Program executions by stage: ``tick``, ``chunk``, ``vision``."""
    out: Dict[str, List[Event]] = {s: [] for s in STAGES}
    for m in modules:
        stage = stage_of(m)
        if stage in out:
            out[stage].append(m)
    return out


def kernel_ops(ops: List[Event], pattern) -> List[Event]:
    return [e for e in ops if pattern.search(e.name)]


def leaves(ops: List[Event]) -> List[Event]:
    """The ops that hold no other op (a ``while`` holds its body's)."""
    return [e for i, e in enumerate(ops)
            if i + 1 >= len(ops) or ops[i + 1].start >= e.end]
