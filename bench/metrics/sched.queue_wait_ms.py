"""Scheduler: median time from a request's scheduled arrival to the start
of the step whose prefill serves it, in ms, over the requests due in the
window that were served."""
import numpy as np


def read(run):
    w = run.window
    waits = [x.first_step_start - x.due for x in w.records
             if w.t0 <= x.due < w.t1 and x.first_step_start is not None]
    if not waits:
        return None
    return float(np.median(waits)) * 1e3
