"""Readings that several per-layer metrics share: the traced window's
stage and kernel events on every chip of the cell, the positions decoded
in it, and roofline shares.

A cell on a ``model=N`` mesh runs each program on all N chips at once,
each chip on its shard. The shares below count the whole cell's least
operations and bytes against the device time summed over its chips, at
one chip's peaks: the share of one chip where the work splits evenly,
never more than the chips really move. On one chip the sum is that
chip's own time, so a one-chip cell reads what it always read."""
from __future__ import annotations

from harness import programs
from harness import trace as T


def chips(run):
    """Device numbers of the cell's chips in the trace, the first
    ``run.chips`` of those that ran an operation: every reading that counts
    chips takes them from here. Empty where the trace of one of them does
    not cover the window that the others cover (the profiler cut it off):
    its first program starts, or its last ends, further from the others'
    than the longest program execution in the window. Such a chip's
    readings would be a part of the window's, so none is made. One chip
    has no other to be compared with."""
    w = run.window
    if w.trace is None:
        return []
    devs = sorted(w.trace.ops)[:run.chips]
    mods = _clipped(run, w.trace.modules, devs)
    if len(devs) < 2 or not any(mods):
        return devs
    if not all(mods):
        return []
    longest = max(m.dur for ms in mods for m in ms)
    firsts = [min(m.start for m in ms) for ms in mods]
    lasts = [max(m.end for m in ms) for ms in mods]
    if (max(firsts) - min(firsts) > longest
            or max(lasts) - min(lasts) > longest):
        return []
    return devs


def _clipped(run, lines, devs):
    a, b = run.window.trace_span
    return [T.clip(lines.get(d, []), a, b) for d in devs]


def stage_events(run, stage: str):
    """Program executions of ``stage`` (tick, chunk, vision) inside the
    traced window, on every chip of the cell: a program that runs on N
    chips gives N events."""
    devs = chips(run)
    if not devs:
        return []
    return [m for mods in _clipped(run, run.window.trace.modules, devs)
            for m in programs.stages(mods)[stage]]


def kernel_events(run, kernel: str):
    """Executions of a Pallas kernel (decode, chunk) inside the traced
    window, on every chip of the cell."""
    devs = chips(run)
    if not devs:
        return []
    pat = {"decode": programs.DECODE_KERNEL,
           "chunk": programs.CHUNK_KERNEL}[kernel]
    return [e for ops in _clipped(run, run.window.trace.ops, devs)
            for e in programs.kernel_ops(ops, pat)]


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
    """Share of the roofline: the least time one chip needs for the whole
    cell's ``flops`` and ``nbytes`` at its peaks, over ``seconds``, the
    device time summed over the cell's chips."""
    if seconds <= 0:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
