"""SOL code generation / execution (the paper's 'SOL generates code for these
and compiles it for the target devices').

On JAX the 'generated code' is a closed-over Python function lowered through
jit.  Per-node implementations are resolved through the backend dispatch table
(``backends.registry``): the election pass annotates ``node.impl`` with the
chosen flavour, and anything unannotated falls back along the chain
backend-specific kernel → shared Pallas kernel → the XLA/jnp reference
lowerings defined below.  This module registers the **reference tier** for
every op it can lower — it knows nothing about which backends exist, so new
backends plug in without touching this file.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .ir import Graph, Module, Node, OpKind
from ..backends import registry

Array = jax.Array


# ---------------------------------------------------------------------------
# individual op lowerings (the reference tier)
# ---------------------------------------------------------------------------

def linear_weight_kn(n: Node, w: Array) -> Array:
    """Normalize a Linear weight to the (K=in, N=out) contraction
    orientation.  Params are stored (out,in) framework-style; the single
    home of the orientation heuristic, shared with the MXU matmul impl."""
    return w.T if w.shape[0] == n.attrs["out_features"] else w


def _lower_linear(n: Node, x: Array, w: Array, b: Array | None,
                  backend: "registry.Backend") -> Array:
    # layout pass decides operand order: 'oi' keeps (out,in) and contracts on
    # the last dim of both; 'io' stores (in,out) — fewer transposes for
    # backends whose matmul wants the reduction dim major (paper Sec. III-A).
    if n.layout == "io":
        y = jnp.einsum("...i,io->...o", x, linear_weight_kn(n, w))
    else:
        wt = w if w.shape[0] == n.attrs["out_features"] else w.T
        y = jnp.einsum("...i,oi->...o", x, wt)
    if b is not None:
        y = y + b
    return y


def _lower_conv2d(n: Node, x: Array, w: Array, b: Array | None,
                  backend: "registry.Backend") -> Array:
    stride = n.attrs.get("stride", 1)
    padding = n.attrs.get("padding", 0)
    groups = n.attrs.get("groups", 1)
    strides = (stride, stride) if isinstance(stride, int) else stride
    pads = ((padding, padding), (padding, padding)) \
        if isinstance(padding, int) else padding
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)
    if b is not None:
        y = y + b[None, :, None, None]
    return y


def _lower_rope(n: Node, x: Array, start: Array | None) -> Array:
    """Rotary embedding of x (B, S, H, hd), ``rotate_half`` layout: the
    position of row ``s`` is ``s``, or ``start[b] + s`` where a start is
    given (a decode step's cache length); ``attrs['inv_freq']`` holds the
    hd/2 frequencies and ``attrs['scale']`` the factor on cos and sin."""
    s = x.shape[1]
    pos = jnp.arange(s)[None, :]
    if start is not None:
        pos = start[:, None] + pos
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        n.attrs["inv_freq"], jnp.float32)
    scale = n.attrs.get("scale", 1.0)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :] * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos + rot * sin).astype(x.dtype)


def _pool(n: Node, x: Array, reduce_fn, init) -> Array:
    k = n.attrs.get("kernel", 2)
    s = n.attrs.get("stride", k)
    ks = (k, k) if isinstance(k, int) else k
    ss = (s, s) if isinstance(s, int) else s
    return jax.lax.reduce_window(
        x, init, reduce_fn, window_dimensions=(1, 1) + ks,
        window_strides=(1, 1) + ss, padding="VALID")


_ELEMENTWISE: Dict[OpKind, Callable[..., Array]] = {
    OpKind.RELU: lambda x: jnp.maximum(x, 0.0),
    OpKind.GELU: jax.nn.gelu,
    OpKind.SILU: jax.nn.silu,
    OpKind.SIGMOID: jax.nn.sigmoid,
    OpKind.TANH: jnp.tanh,
    OpKind.EXP: jnp.exp,
    OpKind.SOFTPLUS: jax.nn.softplus,
    OpKind.IDENTITY: lambda x: x,
}


def _lower_node(n: Node, vals: List[Array], backend: "registry.Backend"
                ) -> Array:
    op = n.op
    if op in _ELEMENTWISE:
        return _ELEMENTWISE[op](vals[0])
    if op is OpKind.ADD:
        return vals[0] + vals[1]
    if op is OpKind.SUB:
        return vals[0] - vals[1]
    if op is OpKind.MUL:
        return vals[0] * vals[1]
    if op is OpKind.DIV:
        return vals[0] / vals[1]
    if op is OpKind.BIAS_ADD:
        x, b = vals
        shape = [1] * x.ndim
        axis = n.attrs.get("axis", -1)
        shape[axis] = b.shape[0]
        return x + b.reshape(shape)
    if op is OpKind.SCALE:
        return vals[0] * n.attrs["value"]
    if op is OpKind.SQRT:
        mv = n.attrs.get("min")
        x = vals[0] if mv is None else jnp.maximum(vals[0], mv)
        return jnp.sqrt(x)
    if op is OpKind.TIME_SHIFT:
        x = vals[0]
        return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    if op is OpKind.ROPE:
        return _lower_rope(n, vals[0], vals[1] if len(vals) > 1 else None)
    if op is OpKind.STACK:
        return jnp.stack(vals)
    if op is OpKind.SOFTCAP:
        c = n.attrs["cap"]
        return jnp.tanh(vals[0] / c) * c
    if op is OpKind.MAXPOOL:
        y = _pool(n, vals[0], jax.lax.max, -jnp.inf)
        mv = n.attrs.get("min_value")
        if mv is not None:          # the folded ReLU (paper's optimization)
            y = jnp.maximum(y, mv)
        return y
    if op is OpKind.AVGPOOL:
        k = n.attrs.get("kernel", 2)
        area = k * k if isinstance(k, int) else k[0] * k[1]
        return _pool(n, vals[0], jax.lax.add, 0.0) / area
    if op is OpKind.GLOBALPOOL:
        return vals[0].mean(axis=(2, 3))
    if op is OpKind.LAYERNORM:
        x, g, b = vals
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + n.attrs.get("eps", 1e-5)) * g + b
    if op is OpKind.RMSNORM:
        x, g = vals
        ms = (x.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
        return (x * jax.lax.rsqrt(ms + n.attrs.get("eps", 1e-6)).astype(x.dtype)) * g
    if op is OpKind.BATCHNORM:
        x, g, b, m, v = vals
        shape = [1, -1] + [1] * (x.ndim - 2)
        inv = jax.lax.rsqrt(v + n.attrs.get("eps", 1e-5))
        return (x - m.reshape(shape)) * (inv * g).reshape(shape) + b.reshape(shape)
    if op is OpKind.SOFTMAX:
        return jax.nn.softmax(vals[0], axis=n.attrs.get("axis", -1))
    if op is OpKind.DROPOUT:
        return vals[0]  # inference identity; training handled by frontend rng
    if op is OpKind.FLATTEN:
        return vals[0].reshape(vals[0].shape[0], -1)
    if op is OpKind.RESHAPE:
        return vals[0].reshape(n.attrs["shape"])
    if op is OpKind.TRANSPOSE:
        return jnp.transpose(vals[0], n.attrs["perm"])
    if op is OpKind.REORDER:
        return vals[0]
    if op is OpKind.LINEAR:
        return _lower_linear(n, vals[0], vals[1],
                             vals[2] if len(vals) > 2 else None, backend)
    if op is OpKind.MATMUL:
        return vals[0] @ vals[1]
    if op is OpKind.CONV2D:
        return _lower_conv2d(n, vals[0], vals[1],
                             vals[2] if len(vals) > 2 else None, backend)
    raise NotImplementedError(f"lowering for {op}")


# ---------------------------------------------------------------------------
# DFP fusion-group reference: compose — under jit, XLA fuses the chain (the
# 'vendor stack' flavour of DFP); numerically identical to the Pallas kernel.
# ---------------------------------------------------------------------------

def compose_fused(n: Node, vals: Sequence[Array],
                  backend: "registry.Backend") -> Array:
    """Lower a FUSED node op-at-a-time; vals are the group's side inputs in
    node.inputs order.  Also the runtime fallback of the Pallas DFP kernel.

    Body ops resolve through the dispatch table too, so a backend's tier-0
    override of a fusable op (say a custom GELU) still applies when the op
    sits inside a composed group."""
    local: Dict[int, Array] = {id(i): v for i, v in zip(n.inputs, vals)}
    out = None
    for b in n.body:
        body_vals = [local[id(i)] for i in b.inputs]
        out = _impl_for(b, backend).fn(b, body_vals, backend)
        local[id(b)] = out
    return out


# ---------------------------------------------------------------------------
# reference-tier registration — invoked by registry._load_entry_points(), not
# at import time, so the executor↔registry import cycle stays one-directional.
# ---------------------------------------------------------------------------

_REFERENCE_OPS = (
    list(_ELEMENTWISE)
    + [OpKind.ADD, OpKind.SUB, OpKind.MUL, OpKind.DIV, OpKind.BIAS_ADD,
       OpKind.SCALE, OpKind.SQRT, OpKind.TIME_SHIFT, OpKind.ROPE,
       OpKind.STACK, OpKind.SOFTCAP,
       OpKind.MAXPOOL, OpKind.AVGPOOL,
       OpKind.GLOBALPOOL, OpKind.LAYERNORM, OpKind.RMSNORM, OpKind.BATCHNORM,
       OpKind.SOFTMAX, OpKind.DROPOUT, OpKind.FLATTEN, OpKind.RESHAPE,
       OpKind.TRANSPOSE, OpKind.REORDER, OpKind.LINEAR, OpKind.MATMUL,
       OpKind.CONV2D]
)


def reference_vjp_grad(n: Node, res, ct, backend: "registry.Backend"):
    """Universal tier-2 backward: ``jax.vjp`` of the op's forward *reference*
    impl, recomputed from the saved primals (remat-style — no extra residuals
    beyond the default ``(inputs, output)`` pair).  Works for any op with a
    registered forward reference, FUSED groups included (vjp of
    ``compose_fused`` re-derives every body op's gradient op-at-a-time)."""
    vals, _out = res
    ref = registry._REFERENCE_IMPLS[n.op]
    diff = [i for i, v in enumerate(vals)
            if jnp.issubdtype(jnp.result_type(v), jnp.inexact)]

    def fwd(*xs):
        full = list(vals)
        for i, x in zip(diff, xs):
            full[i] = x
        return ref.fn(n, full, backend)

    _, pull = jax.vjp(fwd, *[vals[i] for i in diff])
    cts = pull(ct)
    out: List[Any] = [None] * len(vals)
    for i, c in zip(diff, cts):
        out[i] = c
    return tuple(out)


# Ops whose elected forward can be a Pallas kernel (no JAX AD rule) — these
# MUST carry a registered backward for training to ride elected forwards.
# Heavier reference ops join too so their backwards are electable/sweepable;
# plain elementwise/norm ops differentiate through their jnp lowerings.
_GRAD_REFERENCE_OPS = (
    OpKind.LINEAR, OpKind.MATMUL, OpKind.CONV2D, OpKind.AVGPOOL,
    OpKind.FUSED,
)


def _register_reference_impls() -> None:
    for _op in _REFERENCE_OPS:
        registry.register_reference_impl(_op, _lower_node)
    registry.register_reference_impl(OpKind.FUSED, compose_fused,
                                     name="ref.compose", memory="roundtrip")
    for _op in _GRAD_REFERENCE_OPS:
        registry.register_reference_grad_impl(_op, reference_vjp_grad)


# ---------------------------------------------------------------------------
# graph → callable
# ---------------------------------------------------------------------------

def _impl_for(n: Node, backend: "registry.Backend") -> registry.Impl:
    """Honour the election pass's annotation when it is still admissible for
    this backend, else resolve through the fallback chain."""
    if n.impl:
        impl = registry.get_impl(n.impl)
        if impl is not None and impl.op is n.op \
                and impl.admissible(backend, n):
            return impl
    return registry.resolve(backend, n)


def _grad_impl_for(n: Node, backend: "registry.Backend"
                   ) -> registry.Impl | None:
    """Honour the backward election's annotation when still admissible, else
    first admissible backward in the chain; None when the op registers no
    backward (plain JAX AD differentiates its jnp forward impl)."""
    if n.impl_bwd:
        impl = registry.get_grad_impl(n.impl_bwd)
        if impl is not None and impl.op is n.op \
                and impl.admissible(backend, n):
            return impl
    return registry.resolve_grad(backend, n)


def _differentiable_call(n: Node, impl: registry.Impl,
                         grad_impl: registry.Impl,
                         backend: "registry.Backend") -> Callable[..., Any]:
    """Pair a node's elected forward with its elected backward under one
    ``jax.custom_vjp``.  Residuals are the default ``(primal_inputs, output)``
    pair; the backward impl recomputes anything else it needs.  Integer-dtype
    primals (e.g. decode lens) receive ``float0`` cotangents, and float
    cotangents are cast back to the primal dtype so mixed-precision backward
    math (f32 accumulation) round-trips cleanly."""

    @jax.custom_vjp
    def call(*vals):
        return impl.fn(n, list(vals), backend)

    def fwd(*vals):
        out = impl.fn(n, list(vals), backend)
        return out, (vals, out)

    def bwd(res, ct):
        vals, _out = res
        cts = grad_impl.fn(n, res, ct, backend)
        cts = tuple(cts) if isinstance(cts, (tuple, list)) else (cts,)
        if len(cts) != len(vals):
            raise ValueError(
                f"{grad_impl.name} returned {len(cts)} cotangents for "
                f"{len(vals)} inputs of {n}")
        fixed = []
        for v, c in zip(vals, cts):
            if not jnp.issubdtype(jnp.result_type(v), jnp.inexact):
                fixed.append(np.zeros(jnp.shape(v), jax.dtypes.float0))
            elif c is None:
                fixed.append(jnp.zeros_like(v))
            else:
                fixed.append(jnp.asarray(c, dtype=jnp.result_type(v)))
        return tuple(fixed)

    call.defvjp(fwd, bwd)
    return call


def lower_graph(g: Graph, backend: "registry.Backend", *,
                differentiable: bool = False) -> Callable[..., Any]:
    """Return fn(params: dict, *inputs) -> outputs evaluating the graph.

    With ``differentiable=True`` every node whose op registers a backward
    impl is wrapped in ``jax.custom_vjp`` pairing its elected forward with
    its elected backward — the training path's ``jax.grad`` then rides
    elected kernels in both directions.  Mesh note: the ``psum_axes``
    collective stays OUTSIDE the wrapper, so JAX AD transposes it to the
    psum-correct gradient collective for sharded graphs."""
    order = g.topo()
    input_ids = [id(i) for i in g.inputs]
    param_items = sorted(g.params.items())
    impls: Dict[int, registry.Impl] = {
        id(n): _impl_for(n, backend) for n in order
        if n.op not in (OpKind.INPUT, OpKind.PARAM, OpKind.CONST,
                        OpKind.OUTPUT)
    }
    # differentiable lowering: bind custom_vjp wrappers once, at lower time
    calls: Dict[int, Callable[..., Any]] = {}
    if differentiable:
        for n in order:
            if id(n) not in impls:
                continue
            gi = _grad_impl_for(n, backend)
            if gi is not None:
                calls[id(n)] = _differentiable_call(n, impls[id(n)], gi,
                                                    backend)
    # CONST sources bind to fill-constants once; under jit they are baked
    # into the lowered program, never staged from the framework.
    const_vals: Dict[int, Array] = {
        id(n): jnp.full(n.spec.shape, n.attrs.get("fill", 0.0),
                        dtype=n.spec.dtype)
        for n in order if n.op is OpKind.CONST
    }

    def fn(params: Dict[str, Array], *inputs: Array):
        env: Dict[int, Array] = dict(const_vals)
        for nid, x in zip(input_ids, inputs):
            env[nid] = x
        for name, node in param_items:
            env[id(node)] = params[name]
        for n in order:
            if id(n) in env:
                continue
            if n.op in (OpKind.INPUT, OpKind.PARAM):
                raise ValueError(f"unbound source node {n}")
            vals = [env[id(i)] for i in n.inputs]
            call = calls.get(id(n))
            # "op:impl" in the name of every device operation of the node,
            # so a profile ties each operation to its node and election
            with jax.named_scope(f"{n.op.value}:{impls[id(n)].name}"):
                env[id(n)] = (call(*vals) if call is not None
                              else impls[id(n)].fn(n, vals, backend))
            # row-parallel matmuls under shard_map produce partial sums:
            # shard_graph marks them and the collective lowers here, before
            # any downstream bias add (BIAS_ADD is its own node)
            if n.attrs.get("psum_axes"):
                env[id(n)] = jax.lax.psum(env[id(n)],
                                          tuple(n.attrs["psum_axes"]))
        outs = tuple(env[id(o)] for o in g.outputs)
        return outs[0] if len(outs) == 1 else outs

    return fn
