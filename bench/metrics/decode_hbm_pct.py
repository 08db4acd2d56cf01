"""Share of HBM bandwidth the decode steps use: per device step every
weight of the whole model, the KV pages of each live row and the logits
written and read, over the fused ticks' device time summed over the
cell's chips at one chip's peak bandwidth. On a mesh that splits the
weights and pages evenly this is each chip's own share."""
from harness import shapes
from harness.common import decoded_positions, stage_events


def read(run):
    ev = stage_events(run, "tick")
    steps = run.window.counters["device_steps"]
    pos = decoded_positions(run)
    if not ev or not steps:
        return None
    c, e = run.config, run.engine
    nbytes = (steps * shapes.decoder_weight_bytes(c)
              + sum(shapes.paged_decode_attn_bytes(c, p, e["page_size"])
                    for p in pos)
              + steps * e["slots"] * c["vocab_size"] * shapes.BF16 * 2)
    t = sum(x.dur for x in ev)
    return 100.0 * nbytes / (t * run.peaks["hbm_bytes_per_s"])
