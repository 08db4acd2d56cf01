"""Readings that several per-layer metrics share: the traced window's
stage and kernel events, the positions decoded in it, and roofline
shares."""
from __future__ import annotations

from harness import programs
from harness import trace as T


def _device_events(run):
    w = run.window
    if w.trace is None or not w.trace.ops:
        return None
    dev = min(w.trace.ops)
    a, b = w.trace_span
    return (T.clip(w.trace.modules.get(dev, []), a, b),
            T.clip(w.trace.ops[dev], a, b))


def stage_events(run, stage: str):
    """Program executions of ``stage`` (tick, chunk, vision) on the first
    chip inside the traced window."""
    got = _device_events(run)
    if got is None:
        return []
    return programs.stages(*got)[stage]


def kernel_events(run, kernel: str):
    got = _device_events(run)
    if got is None:
        return []
    pat = {"decode": programs.DECODE_KERNEL,
           "chunk": programs.CHUNK_KERNEL}[kernel]
    return programs.kernel_ops(got[1], pat)


def decoded_positions(run):
    """Cache positions of every token a decode step produced inside the
    window: token j >= 1 of a step came from the step at position
    prompt + j - 1 (token 0 comes from the prefill)."""
    w, P = run.window, run.engine["prompt"]
    return [P + j - 1 for s in w.fleet.steps
            for j, t in enumerate(s.token_times)
            if j >= 1 and w.open < t <= w.close]


def prefilled_steps(run):
    """Steps whose prefill finished inside the window."""
    w = run.window
    return [s for s in w.fleet.steps
            if s.t_first is not None and w.open < s.t_first <= w.close]


def roofline_pct(run, flops: float, nbytes: float, seconds: float):
    if seconds <= 0:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
