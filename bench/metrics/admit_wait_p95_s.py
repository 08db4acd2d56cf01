"""95th percentile of the wait from a robot's submit to the engine starting
its prefill (the front end's staging plus the scheduler's queue), on the
client's clock: ``Request.t_submit + Request.queue_s`` is the engine's
admission time."""
import numpy as np


def read(run):
    waits = [s.request.t_submit + s.request.queue_s - s.t_submit
             for s in run.steps]
    return float(np.percentile(waits, 95)) if waits else None
