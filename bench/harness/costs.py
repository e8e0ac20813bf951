"""Operations the served model requires, counted by its model module
(``bench/models/<name>.py``) from the configuration's widths:
``prefill_flops(lm, prompt)`` and ``decode_flops(lm, cache)`` count the
real tokens alone (no padding), the numerator of ``mfu.serve``;
``kernel_work(lm, op, phase, batch, seq)`` gives ``(flops, bytes,
weight)`` of every node of kind ``op`` in one forward of a bucket, from the
bucket's shapes, the numerator of a kernel's roofline share, with the
state-dict name of the weight the node reads (or None).
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, Optional, Sequence, Tuple

from . import weights
from .program import window_forwards
from .trace import prefetch_seconds, scope_seconds


def served_work(w, arch, lm: Dict) -> float:
    """FLOPs the model requires for the tokens served in the window: a
    request's first token costs its prompt's prefill, each later one a
    decoded token over the cache before it."""
    total = 0.0
    for x in w.records:
        plen = len(x.req.prompt)
        for j, t in enumerate(x.tokens):
            if w.t0 <= t < w.t1:
                total += (arch.prefill_flops(lm, plen) if j == 0
                          else arch.decode_flops(lm, plen + j - 1))
    return total


def roofline_s(arch, lm: Dict, ops: Sequence[str],
               forwards: Iterable[Tuple[str, int, int]],
               peaks: Dict[str, float]) -> float:
    """The least time the chip could take for the nodes of kinds ``ops``
    over ``forwards`` (``(phase, batch, seq)`` each): per node, the larger
    of its operations over peak FLOP/s and its bytes over peak bytes/s."""
    total = 0.0
    for (phase, batch, seq), n in Counter(forwards).items():
        for op in ops:
            total += n * sum(
                max(f / peaks["flops"], b / peaks["hbm_bytes_per_s"])
                for f, b, _ in arch.kernel_work(lm, op, phase, batch, seq))
    return total


def roofline_share(run, ops: Sequence[str]) -> Optional[float]:
    """A kernel's share of its roofline in the traced window, in %: the
    least time of the nodes of kinds ``ops`` over the window's bucket
    forwards (``roofline_s``, from the model module's ``kernel_work`` and
    the chip's peaks), over the device time of the operations traced under
    their scopes or under the scopes of the weights they read (XLA puts a
    weight's layout copy there; ``trace.scope_seconds``), and of the
    unscoped copies that stream those weights' rows into them
    (``trace.prefetch_seconds``).  None where the run has no trace, no
    forwards, no such node or no time under their scopes."""
    if not run.records or not run.peaks:
        return None
    forwards = window_forwards(run)
    if not forwards:
        return None
    read = {w for phase, batch, seq in set(forwards) for op in ops
            for _, _, w in run.arch.kernel_work(run.lm, op, phase, batch, seq)
            if w}
    scoped = scope_seconds(run.records, ops, read)
    least = roofline_s(run.arch, run.lm, ops, forwards, run.peaks)
    if least <= 0 or scoped <= 0:
        return None
    shapes = weights.shapes(run.arch.weights(run.lm))
    matrices = {shapes[w] for w in read if len(shapes.get(w, ())) == 2}
    return 100.0 * least / (scoped + prefetch_seconds(run.records, matrices))
