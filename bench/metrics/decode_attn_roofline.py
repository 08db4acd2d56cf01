"""Share of its roofline that the paged decode-attention kernel reaches:
the least time the chip needs for the KV pages read (and the attention
operations) of every token decoded in the traced window, over the
kernel's device time. Memory bounds it at these shapes."""
from harness import shapes
from harness.common import decoded_positions, kernel_events, roofline_pct


def read(run):
    ev = kernel_events(run, "decode")
    pos = decoded_positions(run)
    if not ev or not pos:
        return None
    ps = run.engine["page_size"]
    c = run.config
    nbytes = sum(shapes.paged_decode_attn_bytes(c, p, ps) for p in pos)
    flops = sum(shapes.decode_attn_flops(c, p) for p in pos)
    return roofline_pct(run, flops, nbytes, sum(e.dur for e in ev))
