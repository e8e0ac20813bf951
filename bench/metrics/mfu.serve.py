"""Graph program: FLOPs the model requires for the real tokens served in
the traced window (no padding; counted by the configuration's model module)
over the window times the chip's peak, in %."""
from harness.costs import served_work


def read(run):
    w = run.window
    flops = served_work(w, run.arch, run.lm)
    if not flops or not run.peaks:
        return None
    return 100.0 * flops / ((w.t1 - w.t0) * run.peaks["flops"])
