"""sol.optimize — the paper's user-facing entry point (Listing 1):

    sol_model = sol.optimize(py_model, input_shape)
    sol_model.load_state_dict(py_model.state_dict())
    y = sol_model(x)

The returned SolModel behaves like a framework module (Listing 2): its
parameters stay *framework-managed* (shared storage, version-tracked) while
forward executes SOL's optimized, whole-graph-compiled code.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..backends import Backend, get_backend
from ..core import passes
from ..core.executor import lower_graph
from . import nn
from .extract import extract
from .offload import device as device_api


class SolModel(nn.Module):
    """The custom model SOL injects into the framework (paper Listing 2)."""

    def __init__(self, source: nn.Module, graph, backend, fn, mesh=None):
        super().__init__()
        self._source = source
        self.graph = graph
        self.backend = backend
        self._fn = fn                      # jit'd whole-graph executable
        self.mesh = mesh                   # None = single device
        self._ctx_version = -1
        self._ctx_params: Optional[Dict[str, Any]] = None

    def _params_for_call(self) -> Dict[str, Any]:
        """Offloading context: parameters are cached on the target device and
        re-staged only when the framework-side values change (version bump) —
        the paper's context-caching that limits host↔device memcopies to
        input/output (Sec. V-A).  On a mesh, each parameter is placed with
        the NamedSharding the rule engine assigned it (column/row TP shards
        land directly on their owners; replicated params broadcast once)."""
        v = (self._source.version, device_api.state)
        if self._ctx_params is None or self._ctx_version != v:
            sd = self._source.state_dict()
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                self._ctx_params = {
                    k: jax.device_put(
                        jnp.asarray(sd[k]),
                        NamedSharding(self.mesh, self.graph.param_specs[k]))
                    for k in self.graph.params}
            else:
                self._ctx_params = device_api.stage_params(
                    {k: sd[k] for k in self.graph.params})
            self._ctx_version = v
        return self._ctx_params

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self._source.load_state_dict(sd)

    def state_dict(self) -> Dict[str, Any]:
        return self._source.state_dict()

    def forward(self, *xs) -> Any:
        params = self._params_for_call()
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            staged = [jax.device_put(jnp.asarray(x),
                                     NamedSharding(self.mesh, spec))
                      for x, spec in zip(xs, self.graph.input_specs)]
        else:
            staged = [device_api.stage_input(x) for x in xs]
        y = self._fn(params, *staged)
        if isinstance(y, tuple):     # multi-output graphs (serving prefill/
            return tuple(device_api.fetch_output(o) for o in y)  # decode)
        return device_api.fetch_output(y)

    def stats(self) -> Dict[str, int]:
        return self.graph.stats()

    def impl_report(self, by_kind: bool = False,
                    provenance: bool = False,
                    sol: bool = False) -> Any:
        """Elected-implementation report.  Default: a flat histogram
        (impl name → node count).  With ``by_kind=True``: a per-OpKind
        breakdown ``{op value → {impl name → count}}`` showing which flavour
        the election pass chose for each kind of node on this backend.
        With ``provenance=True``: ``{impl name → {"count": n, "sources":
        {"measured"|"calibrated"|"analytical" → n}, "pinned": [cfg, ...]}}``
        — whether each election came from autotune-cache measurements or the
        cost model, plus any tuned kernel configs the measured elections
        pinned on the nodes (``"pinned"`` only appears when non-empty).
        With ``sol=True``: the speed-of-light view (``core.sol``) — one dict
        per elected node, ranked worst gap first, with the roofline
        ``bound_us``, the measured (or calibrated-estimate) ``us``, their
        ``ratio`` (measured ÷ speed-of-light bound; 1.0 = at the hardware
        limit) and the ``confidence``/``source`` provenance tags — how far
        each elected kernel sits from what the hardware allows."""
        if sol:
            from ..core import autotune
            from ..core import sol as sol_mod
            rows = sol_mod.node_rows(self.graph, self.backend,
                                     autotune.get_cache())
            return [r.to_json() for r in sol_mod.rank(rows)]
        if provenance:
            prov = getattr(self.graph, "election_provenance", {})
            pins = getattr(self.graph, "election_pinned", {})
            out = {}
            for name, count in getattr(self.graph, "elections", {}).items():
                entry = {"count": count,
                         "sources": dict(prov.get(name, {}))}
                if pins.get(name):
                    entry["pinned"] = [tuple(c) for c in pins[name]]
                out[name] = entry
            return out
        if by_kind:
            return {op: dict(impls) for op, impls in
                    getattr(self.graph, "elections_by_op", {}).items()}
        return dict(getattr(self.graph, "elections", {}))

    def check_provenance(self,
                         kinds: Tuple[str, ...] = ("linear", "matmul",
                                                   "attention"),
                         require: Tuple[str, ...] = ("measured",)
                         ) -> list:
        """Serving audit: every node of the given OpKinds must have been
        elected from an allowed provenance source (default: autotune-cache
        measurements).  Returns a list of violation strings — empty means
        every dispatch of those kinds runs an impl the measurement data
        actually elected, not a silent roofline fallback."""
        return provenance_violations(self.impl_report(by_kind=True),
                                     self.impl_report(provenance=True),
                                     kinds=kinds, require=require)


def provenance_violations(by_op: Dict[str, Any], prov: Dict[str, Any],
                          kinds: Tuple[str, ...] = ("linear", "matmul",
                                                    "attention"),
                          require: Tuple[str, ...] = ("measured",)) -> list:
    """Shared audit over the two ``impl_report`` views (works for a live
    ``SolModel`` and a ``DeployedModel`` alike): for each elected impl of
    the target OpKinds, every recorded election source must be in
    ``require``.  An impl with no provenance at all is also a violation —
    silence is not evidence."""
    out = []
    for kind in kinds:
        for impl_name in (by_op.get(kind) or {}):
            sources = (prov.get(impl_name) or {}).get("sources", {})
            bad = {s: n for s, n in sources.items()
                   if s not in require and n}
            if not sources:
                out.append(f"{kind}→{impl_name}: no election provenance "
                           f"recorded")
            elif bad:
                out.append(f"{kind}→{impl_name}: elected via {bad}, "
                           f"require {tuple(require)}")
    return out


def optimize(model: nn.Module, input_shape: Tuple[int, ...], *,
             backend: str | Backend = "xla", training: bool = False,
             dtype: str = "float32", mesh=None) -> SolModel:
    """Extract → optimize → codegen → inject.  ≤1 line for the user.

    With ``mesh`` (a ``jax.sharding.Mesh``) the elected graph compiles
    under ``shard_map``: the TP/DP rule engine partitions it first
    (``distributed.sharding.shard_graph``), so the whole pipeline —
    elections, autotune lookups, Tunable pinning — runs on per-shard
    shapes."""
    graph = extract(model, input_shape, dtype)
    return compile_graph(model, graph, backend, training=training, mesh=mesh)


def compile_graph(model: nn.Module, graph, backend: str | Backend = "xla",
                  *, training: bool = False, mesh=None) -> SolModel:
    """Optimize → codegen → inject for a pre-built graph (the serving
    prefill/decode programs come from ``extract_prefill``/``extract_decode``
    rather than the plain ``extract``); the same pipeline and lowering as
    :func:`optimize`.

    Mesh mode partitions the graph BEFORE ``run_pipeline`` and qualifies the
    backend's autotune-cache key (``mesh_backend``), then wraps the lowered
    executable in ``shard_map`` with the specs the rule engine derived —
    row-parallel psums lower inside the mapped function (executor), and
    shard_map's ``out_specs`` express the gathers at the graph edges."""
    bk = backend if isinstance(backend, Backend) else get_backend(backend)
    if mesh is None:
        graph = passes.run_pipeline(graph, bk, training=training)
        raw_fn = lower_graph(graph, bk, differentiable=training)
        return SolModel(model, graph, bk, jax.jit(raw_fn))

    from ..distributed import sharding as shd
    graph = shd.shard_graph(graph, mesh)
    bk = shd.mesh_backend(bk, mesh)
    graph = passes.run_pipeline(graph, bk, training=training)
    raw_fn = lower_graph(graph, bk, differentiable=training)
    out_specs = (graph.output_specs[0] if len(graph.output_specs) == 1
                 else tuple(graph.output_specs))
    sharded = jax.shard_map(
        raw_fn, mesh=mesh,
        in_specs=(dict(graph.param_specs), *graph.input_specs),
        out_specs=out_specs, check_vma=False)
    return SolModel(model, graph, bk, jax.jit(sharded), mesh=mesh)
