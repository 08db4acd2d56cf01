"""Device milliseconds per run of the chunked-prefill program (one chunk
of ``chunk_size`` rows), from the trace."""
from harness.common import stage_events


def read(run):
    ev = stage_events(run, "chunk")
    if not ev:
        return None
    return 1e3 * sum(e.dur for e in ev) / len(ev)
