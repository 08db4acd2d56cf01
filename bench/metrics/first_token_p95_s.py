"""95th percentile over the window's steps of a robot's submit to its
first token: the queue, the vision tower and the prefill chunks a step
waits through before it decodes. A fleet of robots settles into one of a
few periodic patterns of prefills against ticks, and a run in a rarer one
reads this about 5% lower, so it is a layer metric and carries no bound;
the whole step's tail, ``step_p95_s``, does."""
import numpy as np


def read(run):
    waits = [s.t_first - s.t_submit for s in run.steps
             if s.t_first is not None]
    return float(np.percentile(waits, 95)) if waits else None
