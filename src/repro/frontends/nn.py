"""The 'AI framework' stand-in (paper's PyTorch role).

A deliberately torch-like eager module system: each layer executes as its
own jit-compiled call (op-at-a-time dispatch — the same execution model
that makes eager PyTorch leave fusion opportunities on the table).  SOL
extracts the graph from these modules (extract.py), optimizes it, and
injects a SolModel back (optimize.py) — without touching this file: the
framework's source code never changes, which is the paper's whole point.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class Module:
    """Eager module: owns parameters (host-visible), executes op-by-op."""

    def __init__(self):
        self._params: Dict[str, Array] = {}
        self._children: Dict[str, "Module"] = {}
        self.training = False
        self._version = 0           # bumped on parameter mutation

    # -- parameter plumbing ---------------------------------------------------
    def register(self, name: str, value: Array) -> None:
        self._params[name] = value

    def add_module(self, name: str, mod: "Module") -> None:
        self._children[name] = mod

    def named_parameters(self, prefix: str = "") -> Dict[str, Array]:
        out = {prefix + k: v for k, v in self._params.items()}
        for n, c in self._children.items():
            out.update(c.named_parameters(prefix + n + "."))
        return out

    def load_state_dict(self, sd: Dict[str, Array]) -> None:
        for k, v in sd.items():
            self._set_param(k, v)
        self.bump_version()

    def state_dict(self) -> Dict[str, Array]:
        return self.named_parameters()

    def _set_param(self, dotted: str, value: Array) -> None:
        parts = dotted.split(".")
        mod: Module = self
        for p in parts[:-1]:
            mod = mod._children[p]
        mod._params[parts[-1]] = value

    def bump_version(self) -> None:
        self._version += 1
        for c in self._children.values():
            c.bump_version()

    @property
    def version(self) -> int:
        return self._version

    def train(self, mode: bool = True) -> "Module":
        self.training = mode
        for c in self._children.values():
            c.train(mode)
        return self

    def eval(self) -> "Module":
        return self.train(False)

    def __call__(self, *xs: Array) -> Array:
        # variadic pass-through: SolModel's serving programs (prefill/decode)
        # take multiple inputs; plain layers keep their single-x forward
        return self.forward(*xs)

    def forward(self, x: Array) -> Array:    # pragma: no cover - abstract
        raise NotImplementedError


def _kaiming(key, shape, fan_in):
    return jax.random.normal(key, shape) * math.sqrt(2.0 / fan_in)


_key_counter = [0]


def _next_key():
    _key_counter[0] += 1
    return jax.random.PRNGKey(_key_counter[0])


class Linear(Module):
    def __init__(self, in_features: int, out_features: int,
                 bias: bool = True):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        # framework-native layout: (out, in) — torch convention; SOL's
        # layout pass may elect (in, out) per backend
        self.register("weight", _kaiming(_next_key(),
                                         (out_features, in_features),
                                         in_features))
        self.has_bias = bias
        if bias:
            self.register("bias", jnp.zeros((out_features,)))

    def forward(self, x: Array) -> Array:
        y = _eager_linear(x, self._params["weight"])
        if self.has_bias:
            y = _eager_add_vec(y, self._params["bias"])
        return y


class Conv2d(Module):
    def __init__(self, in_ch: int, out_ch: int, kernel: int, stride: int = 1,
                 padding: int = 0, groups: int = 1, bias: bool = True):
        super().__init__()
        self.attrs = dict(in_ch=in_ch, out_ch=out_ch, kernel=kernel,
                          stride=stride, padding=padding, groups=groups)
        fan_in = in_ch // groups * kernel * kernel
        self.register("weight", _kaiming(
            _next_key(), (out_ch, in_ch // groups, kernel, kernel), fan_in))
        self.has_bias = bias
        if bias:
            self.register("bias", jnp.zeros((out_ch,)))

    def forward(self, x: Array) -> Array:
        a = self.attrs
        y = _eager_conv(x, self._params["weight"], a["stride"],
                        a["padding"], a["groups"])
        if self.has_bias:
            y = _eager_add_chan(y, self._params["bias"])
        return y


class ReLU(Module):
    def forward(self, x: Array) -> Array:
        return _eager_relu(x)


class GELU(Module):
    def forward(self, x: Array) -> Array:
        return _eager_gelu(x)


class MaxPool2d(Module):
    def __init__(self, kernel: int, stride: Optional[int] = None):
        super().__init__()
        self.kernel = kernel
        self.stride = stride or kernel

    def forward(self, x: Array) -> Array:
        return _eager_maxpool(x, self.kernel, self.stride)


class AvgPool2d(Module):
    def __init__(self, kernel: int, stride: Optional[int] = None):
        super().__init__()
        self.kernel = kernel
        self.stride = stride or kernel

    def forward(self, x: Array) -> Array:
        return _eager_avgpool(x, self.kernel, self.stride)


class GlobalAvgPool(Module):
    def forward(self, x: Array) -> Array:
        return _eager_globalpool(x)


class Flatten(Module):
    def forward(self, x: Array) -> Array:
        return x.reshape(x.shape[0], -1)


class LayerNorm(Module):
    def __init__(self, dim: int):
        super().__init__()
        self.dim = dim
        self.register("weight", jnp.ones((dim,)))
        self.register("bias", jnp.zeros((dim,)))

    def forward(self, x: Array) -> Array:
        return _eager_layernorm(x, self._params["weight"],
                                self._params["bias"])


class RMSNorm(Module):
    def __init__(self, dim: int, eps: float = 1e-6):
        super().__init__()
        self.dim = dim
        self.eps = eps
        self.register("weight", jnp.ones((dim,)))

    def forward(self, x: Array) -> Array:
        return _eager_rmsnorm(x, self._params["weight"], self.eps)


class BatchNorm2d(Module):
    def __init__(self, ch: int):
        super().__init__()
        self.ch = ch
        self.register("weight", jnp.ones((ch,)))
        self.register("bias", jnp.zeros((ch,)))
        self.register("running_mean", jnp.zeros((ch,)))
        self.register("running_var", jnp.ones((ch,)))

    def forward(self, x: Array) -> Array:
        p = self._params
        return _eager_batchnorm(x, p["weight"], p["bias"],
                                p["running_mean"], p["running_var"])


class Dropout(Module):
    def __init__(self, p: float = 0.1):
        super().__init__()
        self.p = p

    def forward(self, x: Array) -> Array:
        return x                     # inference identity (eager reference)


class Sequential(Module):
    def __init__(self, *mods: Module):
        super().__init__()
        self.mods = list(mods)
        for i, m in enumerate(mods):
            self.add_module(str(i), m)

    def forward(self, x: Array) -> Array:
        for m in self.mods:
            x = m(x)
        return x

    def __iter__(self):
        return iter(self.mods)


class Residual(Sequential):
    """y = x + chain(x): the multi-input container (transformer / recurrent
    blocks) — extraction emits the inner chain plus an ADD with the skip."""

    def forward(self, x: Array) -> Array:
        y = x
        for m in self.mods:
            y = m(y)
        return x + y


# -- sequence layers (attention + linear recurrences) -------------------------
#
# Eager forwards delegate to the models/ reference functions (flash_mha,
# rglru_seq, rwkv_time_mix_seq); their extraction emitters produce
# ATTENTION / RGLRU_SCAN / RWKV6_SCAN graph nodes so the dispatch table can
# elect the Pallas kernels (see frontends/extract.py).

class Rope:
    """Rotary position embedding tables of one attention layer: the
    ``head_dim // 2`` inverse frequencies (float32, as transformers'
    ``rope_utils`` computes them) and the factor on cos and sin.  Applied in
    the ``rotate_half`` layout to q and k at each row's position."""

    def __init__(self, inv_freq, scale: float = 1.0):
        self.inv_freq = tuple(float(v) for v in
                              np.asarray(inv_freq, np.float32))
        self.scale = float(scale)

    @classmethod
    def default(cls, head_dim: int, theta: float) -> "Rope":
        exps = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
        return cls(1.0 / theta ** exps)

    @classmethod
    def yarn(cls, head_dim: int, theta: float, factor: float,
             original_max_positions: int, beta_fast: float,
             beta_slow: float, attention_factor: float) -> "Rope":
        """YaRN: frequencies whose wavelength fits ``beta_fast`` turns in
        the original context keep their value, those under ``beta_slow``
        turns are divided by ``factor``, and a linear ramp joins the two
        (over dimension indices rounded outwards); cos and sin are scaled
        by ``attention_factor``."""
        def corr_dim(turns: float) -> float:
            return (head_dim * math.log(original_max_positions
                                        / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        extrapolated = 1.0 - ramp
        pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                              / head_dim)
        inv_freq = (1.0 / (factor * pos_freqs) * (1.0 - extrapolated)
                    + 1.0 / pos_freqs * extrapolated)
        return cls(inv_freq, attention_factor)


class MultiHeadAttention(Module):
    """Bias-free multi-head attention with GQA, sliding window, logit
    softcap and an optional rotary embedding (``rope``) of q and k.
    ``head_dim`` defaults to ``d_model // n_heads``.  Weights are stored
    (in, out) — the sequence layers follow the io layout so projections
    extract as MATMUL nodes."""

    def __init__(self, d_model: int, n_heads: int,
                 n_kv_heads: Optional[int] = None, causal: bool = True,
                 window: int = 0, cap: float = 0.0,
                 head_dim: Optional[int] = None,
                 rope: Optional[Rope] = None):
        super().__init__()
        if head_dim is None and d_model % n_heads:
            raise ValueError(f"d_model {d_model} not divisible by {n_heads}")
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads or n_heads
        if n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        self.head_dim = head_dim or d_model // n_heads
        self.causal, self.window, self.cap = causal, window, cap
        self.rope = rope
        hd = self.head_dim
        self.register("wq", _kaiming(_next_key(), (d_model, n_heads * hd),
                                     d_model))
        self.register("wk", _kaiming(_next_key(),
                                     (d_model, self.n_kv_heads * hd), d_model))
        self.register("wv", _kaiming(_next_key(),
                                     (d_model, self.n_kv_heads * hd), d_model))
        self.register("wo", _kaiming(_next_key(), (n_heads * hd, d_model),
                                     n_heads * hd))

    def forward(self, x: Array) -> Array:
        from ..models.flash import flash_mha
        b, s, _ = x.shape
        p = self._params
        hd = self.head_dim
        q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(
            b, s, self.n_heads, hd)
        k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(
            b, s, self.n_kv_heads, hd)
        v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(
            b, s, self.n_kv_heads, hd)
        if self.rope is not None:
            q = _eager_rope(q, self.rope.inv_freq, self.rope.scale)
            k = _eager_rope(k, self.rope.inv_freq, self.rope.scale)
        o = flash_mha(q, k, v, self.causal, self.window, self.cap)
        return jnp.einsum("bse,ed->bsd", o.reshape(b, s, -1), p["wo"])


class MoE(Module):
    """Routed SwiGLU experts: the router scores every row over all
    ``n_experts``, the ``top_k`` best are kept (renormalised over them
    where ``norm_topk``), and each routed expert adds ``down(silu(gate(x))
    · up(x))`` at its weight.  The module holds ``held`` experts, ``first
    .. first + held - 1`` (all of them by default), and adds only their
    part: the share of the layer one chip holds under expert parallelism.
    Weights: ``router`` (d, n_experts); ``w_gate``, ``w_up`` (held, d, f);
    ``w_down`` (held, f, d)."""

    def __init__(self, d_model: int, d_expert: int, n_experts: int,
                 top_k: int, *, held: Optional[int] = None, first: int = 0,
                 norm_topk: bool = True, score: str = "softmax"):
        super().__init__()
        held = n_experts - first if held is None else held
        if first < 0 or held < 1 or first + held > n_experts:
            raise ValueError(f"experts {first}..{first + held - 1} are not "
                             f"among the {n_experts}")
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k {top_k} outside 1..{n_experts}")
        self.d_model, self.d_expert = d_model, d_expert
        self.n_experts, self.held = n_experts, held
        self.attrs = dict(top_k=top_k, first=first, norm_topk=norm_topk,
                          score=score)
        self.register("router", _kaiming(_next_key(), (d_model, n_experts),
                                         d_model))
        for name, shape, fan in (("w_gate", (held, d_model, d_expert),
                                  d_model),
                                 ("w_up", (held, d_model, d_expert), d_model),
                                 ("w_down", (held, d_expert, d_model),
                                  d_expert)):
            self.register(name, _kaiming(_next_key(), shape, fan))

    def forward(self, x: Array) -> Array:
        from ..kernels.moe.ref import moe_ref
        p = self._params
        y = moe_ref(x.reshape(-1, self.d_model), p["router"], p["w_gate"],
                    p["w_up"], p["w_down"], **self.attrs)
        return y.reshape(x.shape)


class RGLRU(Module):
    """Griffin's real-gated linear recurrent unit (the recurrence only):
    h_t = a_t·h_{t-1} + b_t with input/recurrence gates over x: (B,S,D)."""

    def __init__(self, dim: int):
        super().__init__()
        self.dim = dim
        self.register("wa", _kaiming(_next_key(), (dim, dim), dim))
        self.register("wx", _kaiming(_next_key(), (dim, dim), dim))
        # lam init: softplus(lam) ∈ ~(0.3, 1.2) → decay a well inside (0, 1)
        self.register("lam", jax.random.uniform(
            _next_key(), (dim,), minval=0.0, maxval=1.0))

    def forward(self, x: Array) -> Array:
        from ..models.recurrent import rglru_seq
        return rglru_seq(self._params, x)[0]


class RWKV6TimeMix(Module):
    """RWKV6 (Finch) time mix: data-dependent token-shift lerp + LoRA decay
    feeding the WKV linear recurrence, per-head groupnorm, silu gate."""

    def __init__(self, dim: int, n_heads: int, lora_rank: int = 4):
        super().__init__()
        if dim % n_heads:
            raise ValueError(f"dim {dim} not divisible by {n_heads} heads")
        self.dim = dim
        self.n_heads = n_heads
        self.lora_rank = lora_rank
        u01 = lambda shape: jax.random.uniform(_next_key(), shape)
        nrm = lambda shape, s: jax.random.normal(_next_key(), shape) * s
        self.register("mu_x", u01((dim,)))
        for t in ("r", "k", "v", "w", "g"):
            self.register(f"mu_{t}", u01((dim,)))
            self.register(f"lora_a_{t}", nrm((dim, lora_rank), 0.1))
            self.register(f"lora_b_{t}", nrm((lora_rank, dim), 0.1))
        self.register("w0", nrm((dim,), 0.3) - 2.0)   # decay exp(-e^{w0}) ≈ .9
        self.register("u", nrm((dim,), 0.5))
        for t in ("r", "k", "v", "g", "o"):
            self.register(f"w{t}", _kaiming(_next_key(), (dim, dim), dim))
        self.register("gn_gain", 1.0 + nrm((dim,), 0.1))
        self.register("gn_bias", nrm((dim,), 0.1))

    def forward(self, x: Array) -> Array:
        from ..models.recurrent import rwkv_time_mix_seq
        return rwkv_time_mix_seq(self._params, x, self.n_heads)[0]


# -- eager op-at-a-time kernels (each a separate jit = dispatch per layer) ----

@jax.jit
def _eager_linear(x, w):
    return x @ w.T


@jax.jit
def _eager_add_vec(x, b):
    return x + b


@jax.jit
def _eager_add_chan(x, b):
    return x + b[None, :, None, None]


@functools.partial(jax.jit, static_argnames=("stride", "padding", "groups"))
def _eager_conv(x, w, stride, padding, groups):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((padding, padding), (padding, padding)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)


@jax.jit
def _eager_relu(x):
    return jnp.maximum(x, 0.0)


@jax.jit
def _eager_gelu(x):
    return jax.nn.gelu(x)


@functools.partial(jax.jit, static_argnames=("k", "s"))
def _eager_maxpool(x, k, s):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, k, k), (1, 1, s, s), "VALID")


@functools.partial(jax.jit, static_argnames=("k", "s"))
def _eager_avgpool(x, k, s):
    return jax.lax.reduce_window(x, 0.0, jax.lax.add,
                                 (1, 1, k, k), (1, 1, s, s), "VALID") / (k * k)


@jax.jit
def _eager_globalpool(x):
    return x.mean(axis=(2, 3))


@jax.jit
def _eager_layernorm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


@functools.partial(jax.jit, static_argnames=("eps",))
def _eager_rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


@functools.partial(jax.jit, static_argnames=("inv_freq", "scale"))
def _eager_rope(x, inv_freq, scale):
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None] * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@jax.jit
def _eager_batchnorm(x, g, b, m, v):
    inv = jax.lax.rsqrt(v + 1e-5) * g
    return (x - m[None, :, None, None]) * inv[None, :, None, None] \
        + b[None, :, None, None]


# -- model zoo (paper benchmarks: MLP + CNNs) ---------------------------------

def mlp_8192(n_layers: int = 3, features: int = 8192,
             in_features: int = 8192, classes: int = 1000) -> Sequential:
    """The paper's MLP: 3 layers, 8192 features, ReLU."""
    mods: List[Module] = []
    d = in_features
    for _ in range(n_layers - 1):
        mods += [Linear(d, features), ReLU()]
        d = features
    mods.append(Linear(d, classes))
    return Sequential(*mods)


def small_cnn(in_ch: int = 3, classes: int = 10) -> Sequential:
    """VGG-flavoured small CNN (conv-relu-pool blocks → MLP head)."""
    return Sequential(
        Conv2d(in_ch, 32, 3, padding=1), ReLU(), MaxPool2d(2),
        Conv2d(32, 64, 3, padding=1), ReLU(), MaxPool2d(2),
        Conv2d(64, 128, 3, padding=1), BatchNorm2d(128), ReLU(),
        GlobalAvgPool(), Flatten(),
        Linear(128, 256), ReLU(), Dropout(0.1),
        Linear(256, classes),
    )


def transformer_block(d_model: int = 64, n_heads: int = 4,
                      n_kv_heads: Optional[int] = None,
                      mlp_mult: int = 4, causal: bool = True) -> Sequential:
    """Pre-norm transformer block: attention + MLP, both residual."""
    return Sequential(
        Residual(LayerNorm(d_model),
                 MultiHeadAttention(d_model, n_heads, n_kv_heads,
                                    causal=causal)),
        Residual(LayerNorm(d_model), Linear(d_model, mlp_mult * d_model),
                 GELU(), Linear(mlp_mult * d_model, d_model)),
    )


def griffin_block(d_model: int = 64, mlp_mult: int = 2) -> Sequential:
    """RecurrentGemma/Griffin-style block: RG-LRU recurrence + MLP."""
    return Sequential(
        Residual(LayerNorm(d_model), RGLRU(d_model)),
        Residual(LayerNorm(d_model), Linear(d_model, mlp_mult * d_model),
                 GELU(), Linear(mlp_mult * d_model, d_model)),
    )


def rwkv6_block(d_model: int = 64, n_heads: int = 4,
                mlp_mult: int = 2) -> Sequential:
    """RWKV6 (Finch) block: time mix + MLP, both residual."""
    return Sequential(
        Residual(LayerNorm(d_model), RWKV6TimeMix(d_model, n_heads)),
        Residual(LayerNorm(d_model), Linear(d_model, mlp_mult * d_model),
                 GELU(), Linear(mlp_mult * d_model, d_model)),
    )


def depthwise_cnn(in_ch: int = 3, classes: int = 10) -> Sequential:
    """MobileNet-flavoured: depthwise convs (groups == channels) — the
    paper's special case that routes to the DFP module as WeightedPooling."""
    return Sequential(
        Conv2d(in_ch, 32, 3, padding=1), ReLU(),
        Conv2d(32, 32, 3, padding=1, groups=32, bias=False),   # depthwise
        Conv2d(32, 64, 1), ReLU(), MaxPool2d(2),
        Conv2d(64, 64, 3, padding=1, groups=64, bias=False),   # depthwise
        Conv2d(64, 128, 1), ReLU(),
        GlobalAvgPool(), Flatten(), Linear(128, classes),
    )
