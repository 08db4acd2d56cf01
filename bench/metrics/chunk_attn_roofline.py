"""Share of its roofline that the paged chunk-prefill attention kernel
reaches: the least time one chip needs for the causal attention of every
prompt whose prefill finished in the traced window, all heads (operations
bound it), over the kernel's device time summed over the cell's chips,
each of which holds its share of the heads."""
from harness import shapes
from harness.common import kernel_events, prefilled_steps, roofline_pct


def read(run):
    ev = kernel_events(run, "chunk")
    steps = prefilled_steps(run)
    if not ev or not steps:
        return None
    c, P, C = run.config, run.engine["prompt"], run.engine["chunk_size"]
    flops = len(steps) * shapes.chunk_attn_flops(c, 0, P)
    nbytes = len(steps) * sum(
        shapes.chunk_attn_bytes(c, s, min(C, P - s)) for s in range(0, P, C))
    return roofline_pct(run, flops, nbytes, sum(e.dur for e in ev))
