"""sol.deploy — deployment mode (paper Sec. III-C): extract the NN into a
framework-free artifact.

JAX analogue: AOT-export the optimized whole-graph executable via
``jax.export`` (StableHLO bytes + a tiny loader) — the artifact depends on
neither the frontend module system nor the SOL compiler, mirroring the
paper's 'minimalistic library without framework or SOL dependencies'.

The artifact is a zip with three members:

* ``graph.stablehlo``   — the serialized exported executable;
* ``params/<i>.npy``    — one ``.npy`` per parameter leaf, in flatten order
  (parameters may be an arbitrarily nested dict pytree, not just a flat
  dict — the manifest records the tree so load reconstructs it exactly);
* ``manifest.json``     — the parameter tree (shapes/dtypes/leaf indices)
  plus the election metadata of the graph that was exported (impl
  histogram, per-OpKind breakdown, provenance, pinned tunable configs), so
  a server running from the artifact can still audit WHICH implementations
  it is serving — ``DeployedModel.impl_report`` mirrors
  ``SolModel.impl_report``.

Loading stages every parameter host→device exactly ONCE, through
``runtime.packed.transfer`` (one packed DMA for the many small leaves);
``__call__`` then reuses the device-resident buffers instead of re-uploading
host arrays per call.
"""
from __future__ import annotations

import io
import json
import zipfile
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jexport

from ..runtime import packed
from .optimize import SolModel

MANIFEST_SCHEMA = 2


def deploy(sol_model: SolModel,
           input_shape: Optional[Tuple[int, ...]] = None,
           dtype=jnp.float32) -> bytes:
    """Serialize (weights + compiled graph + election metadata) into a
    single artifact.  With ``input_shape=None`` the input specs (shapes AND
    dtypes, e.g. the decode program's int32 ``lens``) are derived from the
    graph's input nodes — required for multi-input graphs like the serving
    decode program."""
    if getattr(sol_model, "mesh", None) is not None:
        raise RuntimeError(
            "deploy: mesh-compiled SolModels cannot be exported — the "
            "artifact format stages params onto one device and the graph's "
            "specs are per-shard local shapes; compile with mesh=None for "
            "artifact export, or serve the mesh model live")
    g = sol_model.graph
    elections = {
        "elections": dict(getattr(g, "elections", {})),
        "by_op": {op: dict(v) for op, v in
                  getattr(g, "elections_by_op", {}).items()},
        "provenance": {k: dict(v) for k, v in
                       getattr(g, "election_provenance", {}).items()},
        "pinned": {k: [list(c) for c in v] for k, v in
                   getattr(g, "election_pinned", {}).items()},
    }
    if input_shape is not None:
        x_specs = [jax.ShapeDtypeStruct(tuple(input_shape), dtype)]
    else:
        x_specs = [jax.ShapeDtypeStruct(tuple(i.spec.shape),
                                        jnp.dtype(i.spec.dtype))
                   for i in g.inputs]
    return export_fn(sol_model._fn, sol_model._params_for_call(),
                     *x_specs, elections=elections,
                     head_rows=sol_model.head_rows)


def export_fn(fn, params, *x_specs: jax.ShapeDtypeStruct,
              elections: Optional[Dict[str, Any]] = None,
              head_rows: Optional[int] = None) -> bytes:
    """Export ``fn(params, *xs)`` plus ``params`` — any (possibly nested)
    dict pytree of arrays — into the artifact format.  ``deploy`` is the
    SolModel front door; this is the general entry point.  ``head_rows``
    is a serving prefill program's (``SolModel.head_rows``)."""
    p_spec = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        params)
    exp = jexport.export(jax.jit(fn))(p_spec, *x_specs)

    leaves: List[np.ndarray] = []
    tree = _tree_spec(params, leaves)
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as z:
        z.writestr("graph.stablehlo", exp.serialize())
        for i, arr in enumerate(leaves):
            z.writestr(f"params/{i}.npy", _npy_bytes(arr))
        manifest = {"schema": MANIFEST_SCHEMA, "tree": tree,
                    "elections": elections or {}, "head_rows": head_rows}
        z.writestr("manifest.json", json.dumps(manifest))
    return buf.getvalue()


def _tree_spec(p, leaves: List[np.ndarray]):
    """Mirror the params pytree as a JSON structure; array leaves become
    ``{"__leaf__": idx, shape, dtype}`` markers and are appended to
    ``leaves`` in deterministic (insertion-order) flatten order."""
    if isinstance(p, dict):
        return {k: _tree_spec(v, leaves) for k, v in p.items()}
    arr = np.asarray(p)
    leaves.append(arr)
    return {"__leaf__": len(leaves) - 1,
            "shape": list(arr.shape), "dtype": str(arr.dtype)}


def _tree_build(spec, staged: List[jax.Array]):
    if isinstance(spec, dict) and isinstance(spec.get("__leaf__"), int):
        return staged[spec["__leaf__"]]
    return {k: _tree_build(v, staged) for k, v in spec.items()}


def _npy_bytes(arr: np.ndarray) -> bytes:
    b = io.BytesIO()
    np.save(b, arr)
    return b.getvalue()


class DeployedModel:
    """Loader for the artifact — no SOL / frontend imports needed beyond
    jax itself (``runtime.packed`` is a 70-line staging helper).

    Parameters are device-put exactly once, here at load time, as one
    packed transfer; every ``__call__`` reuses the staged device buffers."""

    def __init__(self, blob: bytes, device=None):
        z = zipfile.ZipFile(io.BytesIO(blob))
        exp = jexport.deserialize(z.read("graph.stablehlo"))
        manifest = json.loads(z.read("manifest.json"))
        if manifest.get("schema") != MANIFEST_SCHEMA:
            raise ValueError(
                f"artifact manifest schema "
                f"{manifest.get('schema')!r} != {MANIFEST_SCHEMA} — "
                f"written by an incompatible deploy version; re-export "
                f"the artifact")
        if "tree" not in manifest:
            raise ValueError(
                "artifact manifest has no parameter tree (corrupt "
                "artifact?)")
        n_leaves = _count_leaves(manifest["tree"])
        host = [np.load(io.BytesIO(z.read(f"params/{i}.npy")))
                for i in range(n_leaves)]
        staged = packed.transfer(host, device) if host else []
        self.params = _tree_build(manifest["tree"], staged)
        self.staged_leaves = len(staged)
        self._elections = manifest.get("elections") or {}
        self.head_rows = manifest.get("head_rows")
        self._call = exp.call

    def __call__(self, *xs) -> Any:
        return self._call(self.params, *xs)

    # -- election metadata (mirrors SolModel.impl_report) -------------------
    def impl_report(self, by_kind: bool = False,
                    provenance: bool = False) -> Dict[str, Any]:
        """The exported graph's elected-implementation report, recovered
        from the artifact manifest — same shapes of output as
        ``SolModel.impl_report``, so serving audits work identically on a
        live model and a deployed artifact."""
        e = self._elections
        if provenance:
            out = {}
            for name, count in (e.get("elections") or {}).items():
                entry = {"count": count,
                         "sources": dict((e.get("provenance") or {})
                                         .get(name, {}))}
                pins = (e.get("pinned") or {}).get(name)
                if pins:
                    entry["pinned"] = [tuple(c) for c in pins]
                out[name] = entry
            return out
        if by_kind:
            return {op: dict(v) for op, v in (e.get("by_op") or {}).items()}
        return dict(e.get("elections") or {})


def load(blob: bytes, device=None) -> DeployedModel:
    return DeployedModel(blob, device)


def _count_leaves(spec) -> int:
    if isinstance(spec, dict) and isinstance(spec.get("__leaf__"), int):
        return 1
    return sum(_count_leaves(v) for v in spec.values())
