"""Device milliseconds per run of the chunked-prefill program (one chunk
of ``chunk_size`` rows), from the trace: the mean over every run on every
chip of the cell, which is the mean over the chips."""
from harness.common import stage_events


def read(run):
    ev = stage_events(run, "chunk")
    if not ev:
        return None
    return 1e3 * sum(e.dur for e in ev) / len(ev)
