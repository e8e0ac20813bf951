"""Graph extraction: framework modules → SOL IR (paper Sec. III-A,
'extracts the computation graph from the framework').

Extraction is driven by an **emitter registry**, mirroring how kernels
register implementations in the backend dispatch table: one emitter per
framework module type, looked up by exact type then MRO, so new layer kinds
plug into the middleware without touching this file's core walk (the 2022
follow-up paper's maintenance-overhead point).  An emitter receives the
module, the current IR node(s) and an :class:`EmitContext` and returns the
module's output node — containers (`Sequential`, `Residual`) recurse, so
transformer and recurrent blocks extract as genuine multi-input graphs, not
linear chains.

Parameters are registered under their framework dotted names so the SolModel
keeps sharing the framework's parameter storage (paper Listing 2:
'param_0 = ... managed by framework').
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple, Type

import numpy as np

from ..core import ir
from ..core.ir import Graph, Node, OpKind, TensorSpec
from . import nn


class UnsupportedModuleError(TypeError):
    """Raised when no emitter is registered for a module type.  Names the
    offending module's path in the tree and the registered emitters, so the
    fix (``@register_emitter(MyModule)``) is one error message away."""


# ---------------------------------------------------------------------------
# the emitter registry
# ---------------------------------------------------------------------------

# fn(module, ctx, x: Node, path: str) -> Node  (path is the dotted prefix of
# the module in the tree, '' for the root, used for parameter names)
EmitterFn = Callable[[nn.Module, "EmitContext", Node, str], Node]

_EMITTERS: Dict[Type[nn.Module], EmitterFn] = {}

# decode-mode overrides: modules whose forward emitter is sequence-dependent
# but which know how to emit a single-token step against a cache input
# (MultiHeadAttention → DECODE_ATTENTION).  Looked up before _EMITTERS when
# ctx.mode == 'decode'.
_DECODE_EMITTERS: Dict[Type[nn.Module], EmitterFn] = {}

# modules whose forward emitter mixes information across sequence positions
# (scans, token shifts): without a decode emitter they CANNOT be served
# incrementally — a per-token re-emit would silently drop history, so decode
# extraction refuses them loudly instead.
_SEQUENCE_MODULES: set = set()


def register_emitter(*module_types: Type[nn.Module]
                     ) -> Callable[[EmitterFn], EmitterFn]:
    """Register an extraction emitter for one or more module types — the
    frontend analogue of ``backends.registry.register_impl``.  Subclasses
    inherit an emitter through the MRO unless they register their own."""
    def deco(fn: EmitterFn) -> EmitterFn:
        for t in module_types:
            _EMITTERS[t] = fn
        return fn
    return deco


def register_decode_emitter(*module_types: Type[nn.Module]
                            ) -> Callable[[EmitterFn], EmitterFn]:
    """Register a single-token decode emitter: ``x`` is the (B, 1, D) step
    input, and the emitter may create per-layer cache inputs via
    ``ctx.kv_input`` and record this step's cache rows via
    ``ctx.kv_outputs``.  Implies the module is sequence-dependent."""
    def deco(fn: EmitterFn) -> EmitterFn:
        for t in module_types:
            _DECODE_EMITTERS[t] = fn
            _SEQUENCE_MODULES.add(t)
        return fn
    return deco


def mark_sequence_module(*module_types: Type[nn.Module]) -> None:
    """Declare module types position-*dependent* without providing a decode
    emitter: decode extraction will refuse them instead of silently reusing
    the (wrong for one-token steps) forward emitter.  Position-wise modules
    (Linear, LayerNorm, activations, containers) need no declaration."""
    _SEQUENCE_MODULES.update(module_types)


def registered_emitters() -> List[str]:
    """Names of all module types with an emitter (the supported-module set)."""
    return sorted(t.__name__ for t in _EMITTERS)


def _emitter_for(m: nn.Module) -> EmitterFn | None:
    for t in type(m).__mro__:
        if t in _EMITTERS:
            return _EMITTERS[t]
    return None


class EmitContext:
    """Per-extraction state: the parameter table plus node builders shared by
    every emitter.

    ``mode`` selects how sequence layers extract:

    * ``'forward'`` — the training/offline graph (PR 1-4 behaviour);
    * ``'prefill'`` — same compute, but attention layers additionally record
      their per-layer (k, v) projections in ``kv_outputs`` so the server can
      seed each request's KV-cache slot from the prompt forward;
    * ``'decode'``  — single-token step: attention layers read a cache input
      (created via :meth:`kv_input`, ragged lengths in :attr:`lens`) and emit
      ``DECODE_ATTENTION``; sequence-dependent modules without a decode
      emitter are refused.
    """

    def __init__(self, dtype: str = "float32", mode: str = "forward",
                 max_seq: int = 0):
        self.dtype = dtype
        self.mode = mode
        self.max_seq = max_seq
        self.params: Dict[str, Node] = {}
        self.kv_inputs: List[Node] = []    # decode: per-layer cache inputs
        self.kv_outputs: List[Node] = []   # per-layer (k, v) rows, in layer
                                           # order, aligned with kv_inputs
        self.lens: Node | None = None      # decode: (B,) int32 cache lengths
        self.counters: List[Node] = []     # prefill/decode: per-MOE-layer
                                           # int32 (pairs, touched)

    def emit(self, m: nn.Module, x: Node, path: str = "") -> Node:
        if self.mode == "decode":
            for t in type(m).__mro__:
                if t in _DECODE_EMITTERS:
                    return _DECODE_EMITTERS[t](m, self, x, path)
            if any(t in _SEQUENCE_MODULES for t in type(m).__mro__):
                raise UnsupportedModuleError(
                    f"{type(m).__name__} at "
                    f"{path.rstrip('.') or '<root>'} mixes information "
                    f"across sequence positions and has no decode emitter: "
                    f"its forward emitter would silently drop history in a "
                    f"single-token step.  Add one with frontends.extract."
                    f"register_decode_emitter({type(m).__name__}), or serve "
                    f"this model with decode=False (full re-forward).")
        fn = _emitter_for(m)
        if fn is None:
            raise UnsupportedModuleError(
                f"no emitter registered for {type(m).__name__} at "
                f"{path.rstrip('.') or '<root>'} in the module tree; "
                f"registered emitters: {', '.join(registered_emitters())}. "
                f"Add one with frontends.extract."
                f"register_emitter({type(m).__name__}).")
        return fn(m, self, x, path)

    def kv_input(self, shape: Tuple[int, ...], name: str) -> Node:
        """A decode-mode cache input (one per cached tensor per layer); the
        server binds it to the rows gathered from the request's SlotArena
        slot, zero-padded up to the cache bucket."""
        n = ir.input_node(shape, self.dtype, name=name)
        self.kv_inputs.append(n)
        return n

    def param(self, name: str, arr) -> Node:
        if name in self.params:        # same framework storage → same node
            return self.params[name]
        n = ir.param_node(tuple(arr.shape), self.dtype, name=name)
        self.params[name] = n
        return n

    def const(self, shape: Tuple[int, ...], fill: float = 0.0,
              dtype: str | None = None) -> Node:
        return ir.const_node(shape, fill, dtype or self.dtype)

    def matmul(self, x: Node, w: Node) -> Node:
        """x @ w with w in (in, out) layout — the sequence layers' io
        projections."""
        shape = x.spec.shape[:-1] + (w.spec.shape[-1],)
        return Node(OpKind.MATMUL, [x, w], TensorSpec(shape, self.dtype))

    def reshape(self, x: Node, shape: Tuple[int, ...]) -> Node:
        return Node(OpKind.RESHAPE, [x], TensorSpec(tuple(shape), self.dtype),
                    attrs={"shape": tuple(shape)})

    def unary(self, op: OpKind, x: Node, **attrs) -> Node:
        return Node(op, [x], TensorSpec(x.spec.shape, self.dtype),
                    attrs=attrs)

    def binary(self, op: OpKind, a: Node, b: Node) -> Node:
        shape = np.broadcast_shapes(a.spec.shape, b.spec.shape)
        return Node(op, [a, b], TensorSpec(tuple(shape), self.dtype))


# ---------------------------------------------------------------------------
# container emitters
# ---------------------------------------------------------------------------

@register_emitter(nn.Sequential)
def _emit_sequential(m: nn.Sequential, ctx: EmitContext, x: Node,
                     path: str) -> Node:
    cur = x
    for idx, child in enumerate(m):
        cur = ctx.emit(child, cur, f"{path}{idx}.")
    return cur


@register_emitter(nn.Residual)
def _emit_residual(m: nn.Residual, ctx: EmitContext, x: Node,
                   path: str) -> Node:
    # the multi-input node: skip + transformed branch
    return ctx.binary(OpKind.ADD, x, _emit_sequential(m, ctx, x, path))


# ---------------------------------------------------------------------------
# layer emitters (the paper's CNN/MLP scope)
# ---------------------------------------------------------------------------

def _out_shape_conv(x: Tuple[int, ...], m: nn.Conv2d) -> Tuple[int, ...]:
    a = m.attrs
    h = (x[2] + 2 * a["padding"] - a["kernel"]) // a["stride"] + 1
    w = (x[3] + 2 * a["padding"] - a["kernel"]) // a["stride"] + 1
    return (x[0], a["out_ch"], h, w)


def _out_shape_pool(x: Tuple[int, ...], k: int, s: int) -> Tuple[int, ...]:
    return (x[0], x[1], (x[2] - k) // s + 1, (x[3] - k) // s + 1)


@register_emitter(nn.Linear)
def _emit_linear(m: nn.Linear, ctx: EmitContext, x: Node, path: str) -> Node:
    w = ctx.param(path + "weight", m._params["weight"])
    shape = x.spec.shape[:-1] + (m.out_features,)
    cur = Node(OpKind.LINEAR, [x, w], TensorSpec(shape, ctx.dtype),
               attrs={"out_features": m.out_features})
    if m.has_bias:
        b = ctx.param(path + "bias", m._params["bias"])
        cur = Node(OpKind.BIAS_ADD, [cur, b], TensorSpec(shape, ctx.dtype),
                   attrs={"axis": -1})
    return cur


@register_emitter(nn.Conv2d)
def _emit_conv2d(m: nn.Conv2d, ctx: EmitContext, x: Node, path: str) -> Node:
    w = ctx.param(path + "weight", m._params["weight"])
    shape = _out_shape_conv(x.spec.shape, m)
    cur = Node(OpKind.CONV2D, [x, w], TensorSpec(shape, ctx.dtype),
               attrs={"stride": m.attrs["stride"],
                      "padding": m.attrs["padding"],
                      "groups": m.attrs["groups"],
                      "out_channels": m.attrs["out_ch"]})
    if m.has_bias:
        b = ctx.param(path + "bias", m._params["bias"])
        cur = Node(OpKind.BIAS_ADD, [cur, b], TensorSpec(shape, ctx.dtype),
                   attrs={"axis": 1})
    return cur


@register_emitter(nn.ReLU)
def _emit_relu(m, ctx, x, path):
    return ctx.unary(OpKind.RELU, x)


@register_emitter(nn.GELU)
def _emit_gelu(m, ctx, x, path):
    return ctx.unary(OpKind.GELU, x)


@register_emitter(nn.MaxPool2d)
def _emit_maxpool(m: nn.MaxPool2d, ctx, x, path):
    shape = _out_shape_pool(x.spec.shape, m.kernel, m.stride)
    return Node(OpKind.MAXPOOL, [x], TensorSpec(shape, ctx.dtype),
                attrs={"kernel": m.kernel, "stride": m.stride})


@register_emitter(nn.AvgPool2d)
def _emit_avgpool(m: nn.AvgPool2d, ctx, x, path):
    shape = _out_shape_pool(x.spec.shape, m.kernel, m.stride)
    return Node(OpKind.AVGPOOL, [x], TensorSpec(shape, ctx.dtype),
                attrs={"kernel": m.kernel, "stride": m.stride})


@register_emitter(nn.GlobalAvgPool)
def _emit_globalpool(m, ctx, x, path):
    return Node(OpKind.GLOBALPOOL, [x],
                TensorSpec(x.spec.shape[:2], ctx.dtype))


@register_emitter(nn.Flatten)
def _emit_flatten(m, ctx, x, path):
    flat = 1
    for s in x.spec.shape[1:]:
        flat *= s
    return Node(OpKind.FLATTEN, [x],
                TensorSpec((x.spec.shape[0], flat), ctx.dtype))


@register_emitter(nn.LayerNorm)
def _emit_layernorm(m: nn.LayerNorm, ctx, x, path):
    g = ctx.param(path + "weight", m._params["weight"])
    b = ctx.param(path + "bias", m._params["bias"])
    return Node(OpKind.LAYERNORM, [x, g, b],
                TensorSpec(x.spec.shape, ctx.dtype))


@register_emitter(nn.RMSNorm)
def _emit_rmsnorm(m: nn.RMSNorm, ctx, x, path):
    g = ctx.param(path + "weight", m._params["weight"])
    return Node(OpKind.RMSNORM, [x, g], TensorSpec(x.spec.shape, ctx.dtype),
                attrs={"eps": m.eps})


@register_emitter(nn.BatchNorm2d)
def _emit_batchnorm(m: nn.BatchNorm2d, ctx, x, path):
    ps = [ctx.param(path + n, m._params[n]) for n in
          ("weight", "bias", "running_mean", "running_var")]
    return Node(OpKind.BATCHNORM, [x] + ps, TensorSpec(x.spec.shape,
                                                       ctx.dtype))


@register_emitter(nn.Dropout)
def _emit_dropout(m: nn.Dropout, ctx, x, path):
    return ctx.unary(OpKind.DROPOUT, x, p=m.p)


# ---------------------------------------------------------------------------
# sequence-layer emitters: ATTENTION / RGLRU_SCAN / RWKV6_SCAN
# ---------------------------------------------------------------------------

def _rope(m: nn.MultiHeadAttention, ctx: EmitContext, x: Node,
          start: Node | None = None) -> Node:
    """``x`` (B, S, H, hd) with the layer's rotary embedding applied: row
    ``s`` at position ``s`` (a prompt), or ``start[b]`` (a decode step at
    cache length ``start``); ``x`` itself without one."""
    if m.rope is None:
        return x
    return Node(OpKind.ROPE, [x] + ([start] if start is not None else []),
                TensorSpec(x.spec.shape, ctx.dtype),
                attrs={"inv_freq": m.rope.inv_freq, "scale": m.rope.scale})


@register_emitter(nn.MultiHeadAttention)
def _emit_attention(m: nn.MultiHeadAttention, ctx: EmitContext, x: Node,
                    path: str) -> Node:
    b, s, _ = x.spec.shape
    hd = m.head_dim
    q = ctx.reshape(ctx.matmul(x, ctx.param(path + "wq", m._params["wq"])),
                    (b, s, m.n_heads, hd))
    k = ctx.reshape(ctx.matmul(x, ctx.param(path + "wk", m._params["wk"])),
                    (b, s, m.n_kv_heads, hd))
    v = ctx.reshape(ctx.matmul(x, ctx.param(path + "wv", m._params["wv"])),
                    (b, s, m.n_kv_heads, hd))
    q, k = _rope(m, ctx, q), _rope(m, ctx, k)
    att = Node(OpKind.ATTENTION, [q, k, v],
               TensorSpec((b, s, m.n_heads, hd), ctx.dtype),
               attrs={"causal": m.causal, "window": m.window, "cap": m.cap})
    if ctx.mode == "prefill":       # expose this layer's cache rows so the
        ctx.kv_outputs += [k, v]    # server can seed the request's KV slot
    o = ctx.reshape(att, (b, s, m.n_heads * hd))
    return ctx.matmul(o, ctx.param(path + "wo", m._params["wo"]))


@register_decode_emitter(nn.MultiHeadAttention)
def _emit_attention_decode(m: nn.MultiHeadAttention, ctx: EmitContext,
                           x: Node, path: str) -> Node:
    """Single-token step: project q/k/v for the one new position, attend the
    query against this layer's cache input plus the new (k, v) pair via
    DECODE_ATTENTION, and record the pair in ``kv_outputs`` so the server
    appends it to the slot's cache at position ``lens[b]``."""
    if not m.causal:
        raise UnsupportedModuleError(
            f"MultiHeadAttention at {path.rstrip('.') or '<root>'} is "
            f"non-causal: a bidirectional layer cannot be decoded "
            f"incrementally; serve with decode=False.")
    b, s, _ = x.spec.shape
    if s != 1:
        raise ValueError(f"decode extraction expects a single-token step, "
                         f"got sequence length {s}")
    hd = m.head_dim
    q = ctx.reshape(ctx.matmul(x, ctx.param(path + "wq", m._params["wq"])),
                    (b, 1, m.n_heads, hd))
    k_new = ctx.reshape(
        ctx.matmul(x, ctx.param(path + "wk", m._params["wk"])),
        (b, 1, m.n_kv_heads, hd))
    v_new = ctx.reshape(
        ctx.matmul(x, ctx.param(path + "wv", m._params["wv"])),
        (b, 1, m.n_kv_heads, hd))
    q, k_new = _rope(m, ctx, q, ctx.lens), _rope(m, ctx, k_new, ctx.lens)
    cshape = (b, ctx.max_seq, m.n_kv_heads, hd)
    k_cache = ctx.kv_input(cshape, name=f"{path}k_cache")
    v_cache = ctx.kv_input(cshape, name=f"{path}v_cache")
    att = Node(OpKind.DECODE_ATTENTION,
               [q, k_cache, v_cache, k_new, v_new, ctx.lens],
               TensorSpec((b, 1, m.n_heads, hd), ctx.dtype),
               attrs={"window": m.window, "cap": m.cap})
    ctx.kv_outputs += [k_new, v_new]
    o = ctx.reshape(att, (b, 1, m.n_heads * hd))
    return ctx.matmul(o, ctx.param(path + "wo", m._params["wo"]))


@register_emitter(nn.MoE)
def _emit_moe(m: nn.MoE, ctx: EmitContext, x: Node, path: str) -> Node:
    """One MOE node over the rows; in the serving programs also its
    MOE_COUNT, which the programs return beside the logits."""
    ps = [ctx.param(path + k, m._params[k])
          for k in ("router", "w_gate", "w_up", "w_down")]
    if ctx.mode in ("prefill", "decode"):
        ctx.counters.append(Node(
            OpKind.MOE_COUNT, [x, ps[0]], TensorSpec((2,), "int32"),
            attrs=dict(m.attrs, n_held=m.held)))
    return Node(OpKind.MOE, [x] + ps, TensorSpec(x.spec.shape, ctx.dtype),
                attrs=dict(m.attrs))


@register_emitter(nn.RGLRU)
def _emit_rglru(m: nn.RGLRU, ctx: EmitContext, x: Node, path: str) -> Node:
    """models.recurrent.rglru_gates + the RGLRU_SCAN kernel node:
    a = exp(-c·softplus(λ)·sigmoid(x·wa)); b = √(1-a²)·sigmoid(x·wx)·x."""
    from ..models.recurrent import RGLRU_C
    bsz, s, d = x.spec.shape
    wa = ctx.param(path + "wa", m._params["wa"])
    wx = ctx.param(path + "wx", m._params["wx"])
    lam = ctx.param(path + "lam", m._params["lam"])
    r = ctx.unary(OpKind.SIGMOID, ctx.matmul(x, wa))
    i = ctx.unary(OpKind.SIGMOID, ctx.matmul(x, wx))
    decay = ctx.unary(OpKind.SCALE, ctx.unary(OpKind.SOFTPLUS, lam),
                      value=-RGLRU_C)
    a = ctx.unary(OpKind.EXP, ctx.binary(OpKind.MUL, r, decay))
    one_minus_a2 = ctx.binary(OpKind.SUB, ctx.const((1,), 1.0),
                              ctx.binary(OpKind.MUL, a, a))
    gate = ctx.unary(OpKind.SQRT, one_minus_a2, min=1e-12)
    bb = ctx.binary(OpKind.MUL, ctx.binary(OpKind.MUL, gate, i), x)
    h0 = ctx.const((bsz, d), 0.0)
    return Node(OpKind.RGLRU_SCAN, [a, bb, h0],
                TensorSpec((bsz, s, d), ctx.dtype))


@register_emitter(nn.RWKV6TimeMix)
def _emit_rwkv6(m: nn.RWKV6TimeMix, ctx: EmitContext, x: Node,
                path: str) -> Node:
    """models.recurrent.rwkv_time_mix_seq as a graph: token-shift lerp with
    per-target LoRA mixes → r/k/v/decay projections → RWKV6_SCAN → per-head
    groupnorm → silu gate → output projection."""
    bsz, s, d = x.spec.shape
    h, hd = m.n_heads, d // m.n_heads
    P = lambda name: ctx.param(path + name, m._params[name])

    xs = ctx.unary(OpKind.TIME_SHIFT, x)
    dx = ctx.binary(OpKind.SUB, xs, x)
    xm = ctx.binary(OpKind.ADD, x,
                    ctx.binary(OpKind.MUL, dx, P("mu_x")))

    def lora(src: Node, t: str) -> Node:
        inner = ctx.unary(OpKind.TANH, ctx.matmul(src, P(f"lora_a_{t}")))
        return ctx.matmul(inner, P(f"lora_b_{t}"))

    def mixed(t: str) -> Node:
        mix = ctx.binary(OpKind.ADD, P(f"mu_{t}"), lora(xm, t))
        return ctx.binary(OpKind.ADD, x, ctx.binary(OpKind.MUL, dx, mix))

    r = ctx.reshape(ctx.matmul(mixed("r"), P("wr")), (bsz, s, h, hd))
    k = ctx.reshape(ctx.matmul(mixed("k"), P("wk")), (bsz, s, h, hd))
    v = ctx.reshape(ctx.matmul(mixed("v"), P("wv")), (bsz, s, h, hd))
    g = ctx.unary(OpKind.SILU, ctx.matmul(mixed("g"), P("wg")))
    # decay: logw = -exp(w0 + lora_w(m_w)) ≤ 0
    wsum = ctx.binary(OpKind.ADD, P("w0"), lora(mixed("w"), "w"))
    logw = ctx.reshape(ctx.unary(OpKind.SCALE, ctx.unary(OpKind.EXP, wsum),
                                 value=-1.0), (bsz, s, h, hd))
    u = ctx.reshape(P("u"), (h, hd))
    s0 = ctx.const((bsz, h, hd, hd), 0.0)
    o = Node(OpKind.RWKV6_SCAN, [r, k, v, logw, u, s0],
             TensorSpec((bsz, s, h, hd), ctx.dtype))
    # per-head groupnorm == layernorm over the trailing head dim
    gn = Node(OpKind.LAYERNORM, [o, ctx.const((hd,), 1.0),
                                 ctx.const((hd,), 0.0)],
              TensorSpec((bsz, s, h, hd), ctx.dtype), attrs={"eps": 64e-5})
    flat = ctx.reshape(gn, (bsz, s, d))
    scaled = ctx.binary(OpKind.ADD,
                        ctx.binary(OpKind.MUL, flat, P("gn_gain")),
                        P("gn_bias"))
    return ctx.matmul(ctx.binary(OpKind.MUL, scaled, g), P("wo"))


# the recurrent layers have no decode emitter (their state would need its
# own arena region); declaring them sequence-dependent makes decode
# extraction refuse them loudly instead of emitting a history-free step.
mark_sequence_module(nn.RGLRU, nn.RWKV6TimeMix)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def extract(model: nn.Module, input_shape: Tuple[int, ...],
            dtype: str = "float32") -> Graph:
    rank = len(input_shape)
    dims = {4: ir.NCHW(), 3: ir.BSD(), 2: ir.NF()}.get(rank, ())
    x = ir.input_node(input_shape, dtype, dims, name="input")
    ctx = EmitContext(dtype)
    out = ctx.emit(model, x, "")
    g = Graph(inputs=[x], outputs=[out], params=ctx.params)
    g.validate()
    return g


def _counts(ctx: EmitContext) -> List[Node]:
    """The serving programs' last output where the model has MOE layers:
    their (pairs, touched), stacked in layer order, int32 (layers, 2)."""
    if not ctx.counters:
        return []
    return [Node(OpKind.STACK, list(ctx.counters),
                 TensorSpec((len(ctx.counters), 2), "int32"))]


def extract_prefill(model: nn.Module, input_shape: Tuple[int, ...],
                    dtype: str = "float32") -> Graph:
    """The serving prefill program: identical compute to :func:`extract`,
    but every attention layer's (k, v) projections join the graph outputs —
    ``outputs = [logits, k_0, v_0, k_1, v_1, ...]`` in layer order — so one
    prompt forward both produces next-token logits and seeds the request's
    KV-cache slot.  A model with MOE layers adds their counts last
    (:func:`_counts`)."""
    x = ir.input_node(input_shape, dtype, ir.BSD(), name="input")
    ctx = EmitContext(dtype, mode="prefill")
    out = ctx.emit(model, x, "")
    g = Graph(inputs=[x], outputs=[out] + ctx.kv_outputs + _counts(ctx),
              params=ctx.params)
    g.validate()
    return g


def extract_decode(model: nn.Module, batch: int, max_seq: int,
                   d_model: int, dtype: str = "float32") -> Graph:
    """The serving decode program: one token per resident sequence.

    ``inputs  = [x (B, 1, D), lens (B,) int32, k_cache_0, v_cache_0, ...]``
    ``outputs = [logits (B, 1, V), k_new_0, v_new_0, ...]``, and the MOE
    layers' counts last where the model has any (:func:`_counts`).

    Cache inputs are (B, max_seq, KV, hd) with rows ``[0, lens[b])`` valid;
    the new (k, v) outputs are the rows the server appends at position
    ``lens[b]`` after the step.  Sequence-dependent modules without a decode
    emitter raise :class:`UnsupportedModuleError`."""
    x = ir.input_node((batch, 1, d_model), dtype, ir.BSD(), name="step")
    lens = ir.input_node((batch,), "int32", name="lens")
    ctx = EmitContext(dtype, mode="decode", max_seq=max_seq)
    ctx.lens = lens
    out = ctx.emit(model, x, "")
    g = Graph(inputs=[x, lens] + ctx.kv_inputs,
              outputs=[out] + ctx.kv_outputs + _counts(ctx),
              params=ctx.params)
    g.validate()
    return g
