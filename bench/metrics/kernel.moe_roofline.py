"""Kernels: the routed-expert layer's share of its roofline in the traced
window, in %.  Numerator: for every bucket forward of the window (the
program's ``sol.prefill`` and ``sol.decode`` spans, on the trace's clock),
the larger of its expert operations over peak FLOP/s and its expert bytes
over peak bytes/s, from the counters the span carries, summed over the
layers: ``6·d·f`` operations for each of the ``moe_pairs`` (row, expert)
pairs, and for each of the ``moe_touched`` held experts that received any
its three ``d×f`` weights, plus every layer's rows in and out.  Taking the
larger of the sums, not the sum of each layer's larger, reads low, never
high.  Denominator: the device time of the operations under ``moe:`` and
``moe_count:`` scopes, under the scopes of the expert weights (from the
model module's ``kernel_work``), and of the unscoped copies that stream
those weights, or blocks of them, ahead of their use."""
import numpy as np

from harness.program import clock_offset, program_spans, step_spans
from harness.trace import (_ARRAY, _ASYNC_COPIES, base_name, scope_seconds,
                           window_of)
from harness.weights import shapes

OPS = ("moe", "moe_count")


def _forwards(run):
    """``(phase, rows, pairs, touched)`` of the window's bucket forwards
    that carry the counters, or None without spans or a clock offset."""
    spans = program_spans()
    if spans is None or not run.records:
        return None
    trace_steps = [s for s in run.records["spans"] if s[0] == "bench.step"]
    clock = clock_offset(run.named("bench.step"), trace_steps)
    if clock is None:
        return None
    w0, w1 = (x * 1e-9 - clock[0] for x in window_of(run.records))
    out = []
    for name, t0, _, _, a in step_spans(spans):
        if name in ("sol.prefill", "sol.decode") and w0 <= t0 < w1 \
                and "moe_pairs" in a:
            batch, seq = (int(v) for v in a["bucket"].split("x"))
            rows = batch * seq if name == "sol.prefill" else batch
            out.append((rows, a["moe_pairs"], a["moe_touched"]))
    return out


def _stacked_copies(records, stacks):
    """Device seconds of the unscoped asynchronous copies of a stacked
    expert weight of one of the ``stacks`` shapes, or of some of its
    experts."""
    w0, w1 = window_of(records)

    def counted(name):
        opcode, _, ty = base_name(name).partition(" ")
        m = _ARRAY.search(ty)
        if opcode not in _ASYNC_COPIES or not m:
            return False
        dims = tuple(int(x) for x in m.group(1).split(","))
        return len(dims) == 3 and any(dims[1:] == s[1:] and dims[0] <= s[0]
                                      for s in stacks)
    return 1e-9 * sum(d for ops in records["ops"].values()
                      for name, s, d, scope in ops
                      if w0 <= s < w1 and not scope and counted(name))


def read(run):
    if not run.records or not run.peaks:
        return None
    fwd = _forwards(run)
    if not fwd:
        return None
    lm = run.lm
    d, f, n = lm["d_model"], lm["d_expert"], lm["n_layers"]
    item = np.dtype(lm["dtype"]).itemsize
    least = sum(max(6.0 * d * f * pairs / run.peaks["flops"],
                    item * (3 * d * f * touched + 2 * n * rows * d)
                    / run.peaks["hbm_bytes_per_s"])
                for rows, pairs, touched in fwd)
    read_w = {w for _, _, w in run.arch.kernel_work(lm, "moe", "decode", 1, 1)}
    scoped = scope_seconds(run.records, OPS, read_w)
    table = shapes(run.arch.weights(lm))
    stacks = {table[w] for w in read_w if len(table.get(w, ())) == 3}
    spent = scoped + _stacked_copies(run.records, stacks)
    if least <= 0 or spent <= 0:
        return None
    return 100.0 * least / spent
