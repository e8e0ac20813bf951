"""Kernels: the matmul family's share of its roofline in the traced
window, in %.  Numerator: for every bucket forward of the window, the
least time of each ``linear`` node (the MLP and the head) and ``matmul``
node (the attention projections), the larger of its operations over peak
FLOP/s and its bytes over peak bytes/s, counted from the bucket's shapes by
the model module's ``kernel_work``.  Denominator: the device time of the
operations traced under ``linear:`` and ``matmul:`` scopes, any
implementation, under the scopes of the weights those nodes read (the
per-call layout copies XLA makes of them), and of the unscoped copies that
stream those weights' rows into them ahead of the dot."""
from harness.costs import roofline_share


def read(run):
    return roofline_share(run, ("linear", "matmul"))
