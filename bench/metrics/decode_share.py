"""The paper's phase split: per control step, the share of its latency
between its first and its last token (decode), median over the window's
steps, in percent."""
import numpy as np


def read(run):
    shares = [(s.t_done - s.t_first) / (s.t_done - s.t_submit)
              for s in run.steps if s.t_first is not None]
    return 100.0 * float(np.median(shares)) if shares else None
