"""Speed-of-light (SOL) gap analysis — how far is each kernel from the
hardware limit, and where should tuning effort go next?

SOLAR-style closed loop (PAPERS.md): every autotune measurement already
carries the analytic roofline terms of the node it timed (``flops`` and
``nbytes``, recorded by ``core.measure.sweep_node`` from
``passes._node_cost_terms``).  Dividing the measured time by the roofline
bound those terms imply —

    bound_us = HardwareSpec.roofline_s(flops, nbytes) · 1e6
    ratio    = measured_us / bound_us          (1.0 = at the hardware limit)

— ranks every kernel by how much headroom is left.  The bound reuses the
SAME cost model the election pass uses (``passes.node_roofline_terms`` /
``HardwareSpec.roofline_s``), never a parallel formula, so a kernel's gap
is measured against exactly the model that elected it.

Every row carries provenance so a neighbourhood estimate can never
masquerade as a measurement:

* ``confidence`` — ``"exact"``: the shape's own pow2 bucket was measured;
  ``"nearest"``: resolved by nearest-bucket lookup (an estimate).
* ``source`` — ``"measured"``: a wall-clock timing from the cache;
  ``"calibrated"``: estimated from fitted per-(backend, op) roofline
  coefficients; ``"analytical"``: neither (no time estimate at all).

Consumers: ``SolModel.impl_report(sol=True)`` (per elected node of a live
graph), ``benchmarks/run.py sol`` (the ranked table + ``BENCH_sol.json``
artifact), and the gap-driven refinement planner
(``benchmarks/autotune.refine_plan``) which spends its measurement budget
on the worst-ratio cells instead of sweeping uniformly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from .ir import OpKind, SOURCE_OPS


@dataclasses.dataclass
class SolRow:
    """One (op, bucket, dtype, backend, impl) cell of the SOL report."""

    op: str
    bucket: Tuple[int, ...]
    dtype: str
    backend: str
    impl: str
    us: float                       # measured (or calibrated-estimate) time
    bound_us: float                 # roofline bound for the recorded terms
    ratio: float                    # us / bound_us; 0.0 when no bound exists
    bottleneck: str                 # 'compute' | 'memory' | '' (no terms)
    confidence: str                 # 'exact' | 'nearest'
    source: str                     # 'measured' | 'calibrated' | 'analytical'
    config: Optional[Tuple[int, ...]] = None
    flops: float = 0.0
    nbytes: float = 0.0
    node: str = ""                  # node name for graph-scoped reports

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["bucket"] = list(self.bucket)
        if self.config is not None:
            d["config"] = list(self.config)
        return d


def sol_bound_us(hw, flops: float, nbytes: float) -> Tuple[float, str]:
    """Roofline bound in µs plus the dominant term.  Degenerate terms
    (no flops AND no bytes recorded) yield (0.0, '') — the caller reports
    the cell as unbounded-below rather than dividing by zero."""
    bound_s = hw.roofline_s(flops, nbytes)
    if not (bound_s > 0.0) or not math.isfinite(bound_s):
        return 0.0, ""
    dom = "compute" if hw.compute_s(flops) >= hw.memory_s(nbytes) \
        else "memory"
    return bound_s * 1e6, dom


def sol_ratio(us: float, bound_us: float) -> float:
    """measured ÷ bound, guarded to be finite and ≥ 0 for ANY cache entry:
    a missing bound (0.0), a non-finite measurement, or a bound so small
    that the quotient overflows yields 0.0 — such a row ranks as 'no known
    gap', never as an infinite one."""
    if bound_us <= 0.0 or not math.isfinite(bound_us):
        return 0.0
    if us < 0.0 or not math.isfinite(us):
        return 0.0
    ratio = us / bound_us
    return ratio if math.isfinite(ratio) else 0.0


def cache_rows(cache, *, backends: Optional[Sequence[str]] = None,
               best_only: bool = False) -> List[SolRow]:
    """SOL rows for every measurement in an ``AutotuneCache`` (confidence is
    ``"exact"`` by construction: each entry IS its own bucket's
    measurement).  ``best_only`` keeps just the fastest impl per
    (op, bucket, dtype, backend) cell — the elected kernel's row, which is
    what the ranked table and the planner reason about.  Backends unknown
    to the registry are skipped (a cache file can outlive a backend)."""
    from ..backends.registry import available_backends

    known = available_backends()
    rows: List[SolRow] = []
    cells: Dict[Tuple[str, str, str, Tuple[int, ...]], SolRow] = {}
    for (op, dtype, backend), bucket, impl, m in cache.entries():
        if backends is not None and backend not in backends:
            continue
        bk = known.get(backend)
        if bk is None:
            continue
        bound, dom = sol_bound_us(bk.hw, m.flops, m.nbytes)
        row = SolRow(op=op, bucket=bucket, dtype=dtype, backend=backend,
                     impl=impl, us=m.us, bound_us=bound,
                     ratio=sol_ratio(m.us, bound), bottleneck=dom,
                     confidence="exact", source="measured",
                     config=m.config, flops=m.flops, nbytes=m.nbytes)
        rows.append(row)
        cell = (op, dtype, backend, bucket)
        if cell not in cells or row.us < cells[cell].us:
            cells[cell] = row
    return list(cells.values()) if best_only else rows


def node_rows(graph, backend, cache) -> List[SolRow]:
    """Per-elected-node SOL rows for a live graph — the
    ``SolModel.impl_report(sol=True)`` view.  The bound comes from the
    node's own cost terms under the elected impl's memory mode
    (``passes.node_roofline_terms``); the time comes from the cache under
    the node's bucket, tagged ``exact``/``nearest`` by where the lookup
    resolved.  A node whose elected impl has no cached timing falls back
    to the calibrated coefficient estimate when one is fit
    (``source="calibrated"``), else reports ``source="analytical"`` with
    no ratio — silence stays visible, it never fakes a measurement."""
    from ..backends import registry as R
    from . import autotune
    from .passes import node_roofline_terms

    rows: List[SolRow] = []
    for n in graph.topo():
        if n.op in SOURCE_OPS or n.op is OpKind.OUTPUT:
            continue
        impl_name = getattr(n, "impl", None)
        if not impl_name:
            continue
        impl = R.get_impl(impl_name)
        memory = impl.memory if impl is not None else "streamed"
        flops, nbytes, bound_s = node_roofline_terms(n, backend.hw, memory)
        bound, dom = sol_bound_us(backend.hw, flops, nbytes)
        shape = autotune.node_shape(n)
        hits, conf = cache.lookup_with_confidence(
            n.op.value, shape, n.spec.dtype, backend.cache_name)
        m = hits.get(impl_name)
        if m is not None:
            us, source, cfg = m.us, "measured", m.config
        else:
            cal = cache.calibration(backend.cache_name, n.op.value)
            if cal:
                us = (cal["s_per_flop"] * flops
                      + cal["s_per_byte"] * nbytes) * 1e6
                source, conf, cfg = "calibrated", "", None
            else:
                us, source, conf, cfg = 0.0, "analytical", "", None
        rows.append(SolRow(
            op=n.op.value, bucket=autotune.bucket_shape(shape or ()),
            dtype=n.spec.dtype, backend=backend.cache_name, impl=impl_name,
            us=us, bound_us=bound,
            ratio=sol_ratio(us, bound) if source != "analytical" else 0.0,
            bottleneck=dom, confidence=conf, source=source, config=cfg,
            flops=flops, nbytes=nbytes, node=n.name or n.op.value))
    return rows


def rank(rows: Sequence[SolRow]) -> List[SolRow]:
    """Worst gap first.  Exact-bucket measurements rank ahead of
    nearest-bucket estimates and calibrated guesses — an estimate is
    steering data for the planner, but it must never outrank (or be
    mistaken for) a real measurement of the same standing."""
    def key(r: SolRow):
        exact_measured = (r.confidence == "exact" and r.source == "measured")
        return (0 if exact_measured else 1, -r.ratio)
    return sorted(rows, key=key)


def render(rows: Sequence[SolRow], limit: int = 0) -> str:
    """The ranked SOL table ``benchmarks/run.py sol`` prints."""
    hdr = (f"{'backend':17s} {'op':14s} {'bucket':>16s} {'impl':26s} "
           f"{'us':>9s} {'bound_us':>9s} {'ratio':>7s} {'bneck':>7s} "
           f"{'conf':>8s} {'src':>10s}")
    out = [hdr, "-" * len(hdr)]
    for r in (rows[:limit] if limit else rows):
        bucket = "x".join(str(d) for d in r.bucket)
        out.append(
            f"{r.backend:17s} {r.op:14s} {bucket:>16s} {r.impl:26s} "
            f"{r.us:9.1f} {r.bound_us:9.3f} {r.ratio:7.1f} "
            f"{r.bottleneck:>7s} {r.confidence:>8s} {r.source:>10s}")
    return "\n".join(out)
