"""Sharding rule engine: TP/EP/SP/DP PartitionSpecs with divisibility
fallbacks.

Philosophy (the SOL layout pass, at mesh scale): every parameter / cache
tensor is assigned a layout by *name + rank* rules, with hard divisibility
guards — a dim is only sharded when its size divides the mesh axis, else the
engine falls back (heads → head_dim → sequence → replicate).  This is what
makes one rule table serve 10 architectures.

Mesh axes: ``model`` (TP/EP/SP) and ``data`` (+ leading ``pod``) for DP.

Two consumers share the rule table:

* the training path (``param_specs`` / ``batch_specs`` / ``cache_specs``)
  assigns PartitionSpecs to params/batch/cache pytrees of
  ``models/backbone.py``;
* the middleware path (:func:`shard_graph`) threads the SAME name+rank
  rules through a SOL IR graph: it propagates a PartitionSpec per node in
  topo order (Megatron-style TP for attention/MLP pairs, DP on the batch
  axis), rewrites every ``node.spec`` to the per-shard LOCAL shape, and
  annotates row-parallel matmuls with the psum the executor lowers inside
  ``shard_map``.  Because elections/autotuning run on the rewritten graph,
  measured timings and pinned Tunable configs key on post-partition shapes
  (see ``Backend.cache_name``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import backbone as B
from ..models.config import ArchConfig


class ShardingError(ValueError):
    """A graph cannot be partitioned as requested (a sharded dim reaches an
    op that needs it whole, or head counts do not divide the model axis).
    The message names the node and the fix — never a silent wrong answer."""


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _div(size: int, n: int) -> bool:
    return n > 0 and size % n == 0


def shard_dim(mesh: Mesh, size: int, axes):
    """axes if divisible else None (the engine's universal fallback)."""
    return axes if _div(size, axis_size(mesh, axes)) else None


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# 2-D weights sharded on the output (column-parallel)
_COL = {"wq", "wk", "wv", "wg", "wu", "w_in", "w_gate", "ck", "cr",
        "xwq", "xwk", "xwv", "w1", "wr"}
# 2-D weights sharded on the input (row-parallel)
_ROW = {"wo", "wd", "w_out", "cv", "xwo", "w2"}
# 1-D tensors following a column-parallel output
_COL_BIAS = {"bq", "bk", "bv", "b1", "conv_b", "lam"}
_REPLICATED = {"gain", "bias", "bo", "b2", "router", "u", "w0",
               "gn_gain", "gn_bias", "enc_pos"}


def param_spec(mesh: Mesh, cfg: ArchConfig, path: Tuple[str, ...],
               shape: Tuple[int, ...]) -> P:
    name = path[-1]
    stacked = path[0] == "macro"          # leading n_macro scan dim
    lead = (None,) if stacked else ()
    body = shape[1:] if stacked else shape
    m = "model"

    def mk(*spec):
        return P(*(lead + spec))

    parent = path[-2] if len(path) >= 2 else ""
    if parent == "moe" or (len(path) >= 2 and "moe" in path):
        if name == "router":
            return mk(None, None)
        # experts (E, D, F) / (E, F, D): expert-parallel on model
        return mk(shard_dim(mesh, body[0], m), None, None)
    if name == "embed":
        return P(shard_dim(mesh, shape[0], m), None)
    if name == "lm_head":
        return P(None, shard_dim(mesh, shape[1], m))
    if name in _COL and len(body) == 2:
        return mk(None, shard_dim(mesh, body[1], m))
    if name in _ROW and len(body) == 2:
        return mk(shard_dim(mesh, body[0], m), None)
    if name == "conv_w":                   # (W, dr)
        return mk(None, shard_dim(mesh, body[1], m))
    if name in ("wa", "wx"):               # (dr, dr) RG-LRU gates
        return mk(None, shard_dim(mesh, body[1], m))
    if name in _COL_BIAS and len(body) == 1:
        return mk(shard_dim(mesh, body[0], m))
    if name.startswith("lora_") or name.startswith("mu_"):
        return mk(*(None,) * len(body))
    if name in _REPLICATED or len(body) == 1:
        return mk(*(None,) * len(body))
    return mk(*(None,) * len(body))


def param_specs(mesh: Mesh, cfg: ArchConfig, params_tree) -> Any:
    """PartitionSpec pytree matching a params(-shaped) pytree."""
    def walk(path, leaf):
        names = tuple(
            p.key if hasattr(p, "key") else str(p.idx) for p in path)
        return param_spec(mesh, cfg, names, tuple(leaf.shape))
    return jax.tree_util.tree_map_with_path(walk, params_tree)


# ---------------------------------------------------------------------------
# batch / cache rules
# ---------------------------------------------------------------------------

def batch_specs(mesh: Mesh, cfg: ArchConfig, batch_tree) -> Any:
    dp = dp_axes(mesh)

    def walk(path, leaf):
        b = shard_dim(mesh, leaf.shape[0], dp)
        return P(b, *(None,) * (len(leaf.shape) - 1))
    return jax.tree_util.tree_map_with_path(walk, batch_tree)


def cache_specs(mesh: Mesh, cfg: ArchConfig, cache_tree) -> Any:
    """KV caches: batch on data; kv-heads on model when divisible, else
    sequence-sharded (SP / flash-decoding); recurrent states: channels/heads
    on model."""
    dp = dp_axes(mesh)
    m = "model"

    def walk(path, leaf):
        names = [p.key if hasattr(p, "key") else "" for p in path]
        stacked = names and names[0] == "macro"
        shape = leaf.shape[1:] if stacked else leaf.shape
        lead = (None,) if stacked else ()

        def mk(*spec):
            return P(*(lead + spec))

        last = names[-1] if names else ""
        bspec = shard_dim(mesh, shape[0], dp)
        if last == "S":            # rwkv state (B, H, hd, hd)
            return mk(bspec, shard_dim(mesh, shape[1], m), None, None)
        if last == "h":            # rglru hidden (B, dr)
            return mk(bspec, shard_dim(mesh, shape[1], m))
        if last == "conv":         # (B, W-1, dr)
            return mk(bspec, None, shard_dim(mesh, shape[2], m))
        if last in ("last_x", "last_xc"):
            return mk(bspec, None)
        if len(shape) == 4:        # attention kv cache (B, S, KV, hd)
            kv_ax = shard_dim(mesh, shape[2], m)
            if kv_ax is not None:
                return mk(bspec, None, kv_ax, None)
            return mk(bspec, shard_dim(mesh, shape[1], m), None, None)
        return mk(bspec, *(None,) * (len(shape) - 1))
    return jax.tree_util.tree_map_with_path(walk, cache_tree)


def named(mesh: Mesh, spec_tree) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# the middleware path: PartitionSpec propagation over a SOL IR graph
# ---------------------------------------------------------------------------

def mesh_backend(backend, mesh: Mesh):
    """The per-mesh view of a dispatch-table backend: same ``name`` (so
    tier-0 impls and capabilities match unchanged) but a ``shard_tag``
    qualifying every autotune-cache key.  Without the tag, a per-shard
    bucket could collide with a global-shape bucket — a local pow2 shape
    divided by a pow2 mesh axis IS some other global bucket — and a mesh
    election would silently serve a flat-backend timing."""
    tag = "".join(f"{a}{mesh.shape[a]}" for a in mesh.axis_names)
    return dataclasses.replace(backend, shard_tag=tag)


def _entry(spec: P, i: int, rank: int):
    """The sharding of dim ``i`` (supports negative) under ``spec``; specs
    shorter than the rank are replicated on the trailing dims."""
    if i < 0:
        i += rank
    return spec[i] if 0 <= i < len(spec) else None


def _axes_tuple(e) -> Tuple[str, ...]:
    if e is None:
        return ()
    return (e,) if isinstance(e, str) else tuple(e)


def _local_shape(mesh: Mesh, shape: Tuple[int, ...], spec: P
                 ) -> Tuple[int, ...]:
    return tuple(d // axis_size(mesh, _entry(spec, i, len(shape)))
                 for i, d in enumerate(shape))


def shard_graph(g, mesh: Mesh):
    """Partition a freshly-extracted SOL graph for ``mesh`` — the rule
    table threaded through the middleware rather than bolted on beside it.

    In one topo walk the engine (1) assigns every node a PartitionSpec of
    its GLOBAL shape — DP on the batch dim of inputs, Megatron-style TP for
    attention (wq/wk/wv column-parallel so heads stay shard-local, wo
    row-parallel) and for MLP pairs (column → elementwise → row), KV caches
    sharded on the kv-head axis to match the column-parallel projections —
    then (2) rewrites every ``node.spec`` (and shape-bearing attrs:
    RESHAPE targets, LINEAR ``out_features``) to the per-shard LOCAL shape,
    and (3) annotates row-parallel LINEAR/MATMUL nodes with
    ``attrs['psum_axes']`` — the collective the executor lowers right after
    the partial matmul, BEFORE any downstream bias add (extraction emits
    BIAS_ADD as its own node, so the ordering is structural).

    Because the rewrite happens before ``passes.run_pipeline``, elections,
    autotune lookups, Tunable pinning and ``strict_provenance`` all see
    post-partition shapes; paired with ``mesh_backend``'s cache qualifier,
    mesh timings and flat timings can never alias.

    Every sharding decision is guarded by divisibility (``shard_dim``) and
    falls back to replication; a sharded dim reaching an op that needs it
    whole raises :class:`ShardingError` naming the node.  Returns ``g``
    with ``g.mesh`` / ``g.input_specs`` / ``g.output_specs`` /
    ``g.param_specs`` attached for the shard_map compile."""
    from ..core.ir import OpKind, TensorSpec

    dp = dp_axes(mesh)
    m = "model" if "model" in mesh.axis_names else None
    mp = axis_size(mesh, m)
    spec: Dict[int, P] = {}
    cons = g.consumers()
    param_name = {id(n): name for name, n in g.params.items()}
    order = list(g.topo())

    def pspec(node) -> P:
        s = spec.get(id(node))
        if s is None:
            s = P(*([None] * len(node.spec.shape)))
            spec[id(node)] = s
        return s

    def ent(node, i):
        return _entry(pspec(node), i, len(node.spec.shape))

    # -- global feasibility: head-parallel attention needs every layer's
    #    query AND kv head counts divisible by the model axis (a partially
    #    sharded q/k/v set would make the attention node non-local)
    attn_tp = mp > 1
    for n in order:
        if n.op in (OpKind.ATTENTION, OpKind.DECODE_ATTENTION):
            heads = n.spec.shape[2]
            kv = n.inputs[1].spec.shape[2]
            if heads % mp or kv % mp:
                attn_tp = False

    _LOCAL_CHAIN = {OpKind.BIAS_ADD, OpKind.RELU, OpKind.GELU, OpKind.SILU,
                    OpKind.SIGMOID, OpKind.TANH, OpKind.EXP, OpKind.SOFTPLUS,
                    OpKind.SQRT, OpKind.SCALE, OpKind.SOFTCAP,
                    OpKind.DROPOUT, OpKind.IDENTITY}
    _ELEMENTWISE = _LOCAL_CHAIN - {OpKind.BIAS_ADD}

    def _col_ok(n) -> bool:
        """Column-sharding ``n``'s output feature dim is legal when the
        sharded activation stays shard-local (bias/unary elementwise) until
        a row-parallelizable matmul folds it back — or until the graph edge,
        where shard_map's out_specs gather it (vocab-parallel head)."""
        cur = n
        while True:
            users = cons.get(cur, [])
            if not users:
                return cur in g.outputs
            if len(users) != 1:
                return False
            u = users[0]
            if u.op in _LOCAL_CHAIN:
                cur = u
                continue
            return (u.op in (OpKind.LINEAR, OpKind.MATMUL)
                    and u.inputs[0] is cur
                    and u.inputs[1].op is OpKind.PARAM
                    and _div(u.inputs[1].spec.size
                             // max(u.spec.shape[-1], 1), mp))

    def _attn_proj(n):
        """True when ``n`` is an attention q/k/v projection: its sole
        consumer is a RESHAPE feeding ATTENTION / DECODE_ATTENTION."""
        users = cons.get(n, [])
        if len(users) == 1 and users[0].op is OpKind.RESHAPE:
            nxt = cons.get(users[0], [])
            return (len(nxt) == 1
                    and nxt[0].op in (OpKind.ATTENTION,
                                      OpKind.DECODE_ATTENTION))
        return False

    def _matmul(n):
        x, w = n.inputs[0], n.inputs[1]
        rank = len(x.spec.shape)
        sx = tuple(_entry(pspec(x), i, rank) for i in range(rank))
        xlast = sx[-1]
        out_dim = n.spec.shape[-1]
        # weight orientation: LINEAR params are stored (out, in)
        # framework-style; MATMUL weights are (in, out)
        oi = n.op is OpKind.LINEAR

        def wspec(in_ax, out_ax) -> P:
            return P(out_ax, in_ax) if oi else P(in_ax, out_ax)

        if w.op is not OpKind.PARAM:
            if xlast is not None or ent(w, 0) is not None:
                raise ShardingError(
                    f"{n.name}: contraction dim is sharded but the weight "
                    f"is not a parameter — no rule to row-parallelize it")
            spec[id(n)] = P(*(tuple(sx)[: rank - 1]
                              + (ent(w, -1),)))
            return
        have = spec.get(id(w))
        if xlast is not None:
            # row-parallel: weight sharded on its input dim, partial sums
            # psum'd over the contraction axes right after this node
            want = wspec(xlast, None)
            if have is not None and have != want:
                raise ShardingError(
                    f"{n.name}: shared param "
                    f"{param_name.get(id(w), w.name)!r} already sharded as "
                    f"{have}, row-parallel use needs {want}")
            spec[id(w)] = want
            n.attrs["psum_axes"] = _axes_tuple(xlast)
            spec[id(n)] = P(*(tuple(sx)[: rank - 1] + (None,)))
            return
        col = False
        if m is not None and have is None and _div(out_dim, mp):
            col = attn_tp if _attn_proj(n) else _col_ok(n)
        if col:
            spec[id(w)] = wspec(None, m)
            # batch dims follow the activation; features land on the model axis
            spec[id(n)] = P(*(tuple(sx)[: rank - 1] + (m,)))
        else:
            if have is None:
                spec[id(w)] = wspec(None, None)
            out_ax = _entry(spec[id(w)], 0 if oi else -1,
                            len(w.spec.shape))
            spec[id(n)] = P(*(tuple(sx)[: rank - 1] + (out_ax,)))

    def _reshape(n):
        src = n.inputs[0]
        a, b = src.spec.shape, tuple(n.attrs["shape"])
        sin = pspec(src)
        ra = len(a)
        if len(b) == ra + 1 and a[:-1] == b[:-2] and a[-1] == b[-2] * b[-1]:
            # split last dim, e.g. (B,S,H·hd) → (B,S,H,hd): a feature shard
            # holds whole heads (attn_tp guarantees H % mp == 0), so the
            # shard moves to the head axis
            spec[id(n)] = P(*(tuple(_entry(sin, i, ra) for i in range(ra))
                              + (None,)))
            return
        if len(b) == ra - 1 and a[:-2] == b[:-1] and b[-1] == a[-2] * a[-1]:
            # merge last two dims, e.g. (B,S,H,hd) → (B,S,H·hd)
            if _entry(sin, -1, ra) is not None:
                raise ShardingError(
                    f"{n.name}: cannot merge a sharded trailing dim")
            spec[id(n)] = P(*tuple(_entry(sin, i, ra)
                                   for i in range(ra - 1)))
            return
        if any(_entry(sin, i, ra) is not None for i in range(ra)
               if not (i == 0 and b and b[0] == a[0])):
            raise ShardingError(
                f"{n.name}: general reshape of a sharded tensor "
                f"({a} → {b} under {sin}) has no propagation rule")
        lead = _entry(sin, 0, ra) if b and a and b[0] == a[0] else None
        spec[id(n)] = P(*((lead,) + (None,) * (len(b) - 1)))

    def _attention(n):
        names = ("q", "k", "v", "k_new", "v_new")
        head_ents = {ent(q, 2) for q in n.inputs
                     if len(q.spec.shape) == 4}
        if len(head_ents) > 1:
            raise ShardingError(
                f"{n.name}: inconsistent head sharding across operands "
                f"({head_ents}) — the model axis must divide every "
                f"layer's n_heads and n_kv_heads, or none ({names})")
        spec[id(n)] = pspec(n.inputs[0])

    for n in order:
        op = n.op
        shape = n.spec.shape
        rank = len(shape)
        if op is OpKind.INPUT:
            bspec = shard_dim(mesh, shape[0], dp) if rank else None
            if (rank == 4 and m is not None
                    and n.name.endswith(("k_cache", "v_cache"))):
                kv = shard_dim(mesh, shape[2], m) if attn_tp else None
                spec[id(n)] = P(bspec, None, kv, None)
            else:
                spec[id(n)] = P(*((bspec,) + (None,) * (rank - 1)))
            continue
        if op in (OpKind.PARAM, OpKind.CONST):
            continue                       # params: assigned by consumers;
                                           # consts: replicated (lazily)
        if op in (OpKind.LINEAR, OpKind.MATMUL):
            _matmul(n)
        elif op is OpKind.RESHAPE:
            _reshape(n)
        elif op in (OpKind.ATTENTION, OpKind.DECODE_ATTENTION):
            _attention(n)
        elif op is OpKind.BIAS_ADD:
            x, b = n.inputs[0], n.inputs[1]
            ax = n.attrs.get("axis", -1)
            want = P(ent(x, ax))
            have = spec.get(id(b))
            if have is not None and have != want:
                raise ShardingError(
                    f"{n.name}: bias already sharded as {have}, "
                    f"needs {want}")
            spec[id(b)] = want
            spec[id(n)] = pspec(x)
        elif op in (OpKind.LAYERNORM, OpKind.RMSNORM):
            if ent(n.inputs[0], -1) is not None:
                raise ShardingError(
                    f"{n.name}: normalization over a model-sharded feature "
                    f"dim — insert the psum/row-parallel matmul before the "
                    f"norm (serving graphs normalize replicated "
                    f"activations)")
            spec[id(n)] = pspec(n.inputs[0])
        elif op is OpKind.SOFTMAX:
            if ent(n.inputs[0], n.attrs.get("axis", -1)) is not None:
                raise ShardingError(
                    f"{n.name}: softmax over a sharded axis")
            spec[id(n)] = pspec(n.inputs[0])
        elif op in _ELEMENTWISE:
            spec[id(n)] = pspec(n.inputs[0])
        elif op is OpKind.TIME_SHIFT:
            if ent(n.inputs[0], 1) is not None:
                raise ShardingError(f"{n.name}: shift along a sharded axis")
            spec[id(n)] = pspec(n.inputs[0])
        elif op is OpKind.TAKE_ROWS:
            # each batch entry's row is picked along the sequence axis: the
            # batch may be data-sharded, the rows and features are whole,
            # and every shard holds the indices of its own entries
            x, idx = n.inputs
            if any(ent(x, i) is not None for i in range(1, rank)):
                raise ShardingError(
                    f"{n.name}: row selection over a sharded sequence or "
                    f"feature axis")
            if ent(idx, 0) != ent(x, 0):
                raise ShardingError(
                    f"{n.name}: indices sharded as {pspec(idx)}, the batch "
                    f"as {pspec(x)}")
            spec[id(n)] = P(ent(x, 0), *([None] * (rank - 1)))
        elif op in (OpKind.ADD, OpKind.SUB, OpKind.MUL, OpKind.DIV):
            out: List[Any] = []
            for i in range(rank):
                ents = []
                for inp in n.inputs:
                    off = rank - len(inp.spec.shape)
                    if i - off >= 0 and inp.spec.shape[i - off] > 1:
                        ents.append(ent(inp, i - off))
                if len(set(ents)) > 1:
                    raise ShardingError(
                        f"{n.name}: operands disagree on dim {i} sharding "
                        f"({ents})")
                out.append(ents[0] if ents else None)
            spec[id(n)] = P(*out)
        elif op is OpKind.TRANSPOSE:
            sin = pspec(n.inputs[0])
            ri = len(n.inputs[0].spec.shape)
            spec[id(n)] = P(*(_entry(sin, p, ri) for p in n.attrs["perm"]))
        elif op is OpKind.FLATTEN:
            if any(ent(n.inputs[0], i) is not None
                   for i in range(1, len(n.inputs[0].spec.shape))):
                raise ShardingError(f"{n.name}: flatten of a sharded tensor")
            spec[id(n)] = P(ent(n.inputs[0], 0), None)
        else:
            # batch-preserving default (convs, pools, norms over channels,
            # scans): model-sharded inputs have no rule here
            for inp in n.inputs:
                ri = len(inp.spec.shape)
                if any(_entry(pspec(inp), i, ri) is not None
                       for i in range(1, ri)):
                    raise ShardingError(
                        f"{n.name} ({op.value}): no sharding-propagation "
                        f"rule for a model-sharded operand")
            lead = ent(n.inputs[0], 0) if n.inputs and rank else None
            spec[id(n)] = P(*((lead,) + (None,) * max(rank - 1, 0)))

    # -- rewrite every node to its per-shard LOCAL shape -------------------
    for n in order:
        s = pspec(n)
        local = _local_shape(mesh, n.spec.shape, s)
        if local != n.spec.shape:
            n.spec = dataclasses.replace(n.spec, shape=local)
        if n.op is OpKind.RESHAPE:
            n.attrs["shape"] = local
        if n.op is OpKind.LINEAR:
            f = axis_size(mesh, _entry(s, -1, len(local)))
            if f > 1:
                n.attrs["out_features"] = n.attrs["out_features"] // f

    g.mesh = mesh
    g.input_specs = [spec[id(i)] for i in g.inputs]
    g.output_specs = [pspec(o) for o in g.outputs]
    g.param_specs = {name: pspec(node) for name, node in g.params.items()}
    g.validate()
    return g
