"""Model FLOP utilization of the window: the operations of the control
steps completed in it (vision tower, prefill, decode, from the
configuration's shapes) over the window's length times the chips' peak."""
from harness import shapes


def read(run):
    w = run.window
    new = run.traffic["cot_tokens"] + run.traffic["action_tokens"]
    per_step = shapes.control_step_flops(run.config, run.engine["prompt"],
                                         new)
    return 100.0 * len(run.steps) * per_step / (
        (w.close - w.open) * run.chips * run.peaks["bf16_flops_per_s"])
