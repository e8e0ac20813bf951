"""Scheduler: median host time of a ``SolServer.step`` outside its staging
and bucket-program spans, in ms.  In the traced run a bucket program's span
waits for its outputs, so what is left of a step is host work: admission,
batch selection, gathering and padding cache rows, sampling, appends."""
import numpy as np


def read(run):
    steps = run.named("bench.step")
    if not steps:
        return None
    host = [(s[2] - s[1])
            - sum(c[2] - c[1] for c in run.inside(s, "bench.stage"))
            - sum(c[2] - c[1] for c in run.inside(s, "bench.forward"))
            for s in steps]
    return float(np.median(host)) * 1e3
