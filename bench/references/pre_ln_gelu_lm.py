"""Plain reference of the served LM: pre-norm blocks of multi-head attention
(grouped KV heads: query head ``h`` reads KV head ``h // (n_heads //
n_kv_heads)``, causal, scaled by ``head_dim ** -0.5``) and a tanh-GELU MLP,
both residual, then an output head with a bias.

Straight ``jax.numpy``, one layer at a time, imported from nothing of the
program.  In float32 every matmul runs at ``highest`` precision; with
``dtype=bfloat16`` (the control of the comparison) weights, activations and
every intermediate are bfloat16 and matmuls run at default precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
PAD = 256                # sequence lengths are padded to a multiple of this


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "prec"))
def _layer(x, p, n_heads, n_kv, prec):
    s, d = x.shape
    hd = d // n_heads
    mm = functools.partial(jnp.matmul, precision=prec)
    h = _ln(x, p["ln1_g"], p["ln1_b"])
    q = mm(h, p["wq"]).reshape(s, n_kv, n_heads // n_kv, hd)
    k = mm(h, p["wk"]).reshape(s, n_kv, hd)
    v = mm(h, p["wv"]).reshape(s, n_kv, hd)
    logits = jnp.einsum("qkgd,skd->kgqs", q, k, precision=prec)
    logits = logits * jnp.asarray(hd ** -0.5, x.dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", probs.astype(x.dtype), v,
                   precision=prec).reshape(s, d)
    x = x + mm(o, p["wo"])
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    h = jax.nn.gelu(mm(h, p["w1"].T) + p["b1"])
    return x + mm(h, p["w2"].T) + p["b2"]


@functools.partial(jax.jit, static_argnames=("prec",))
def _head(x, w, b, prec):
    return jnp.matmul(x, w.T, precision=prec) + b


def _layer_params(params, i, dtype):
    a, m = f"{i}.0.", f"{i}.1."
    names = {"ln1_g": a + "0.weight", "ln1_b": a + "0.bias",
             "wq": a + "1.wq", "wk": a + "1.wk", "wv": a + "1.wv",
             "wo": a + "1.wo", "ln2_g": m + "0.weight",
             "ln2_b": m + "0.bias", "w1": m + "1.weight", "b1": m + "1.bias",
             "w2": m + "3.weight", "b2": m + "3.bias"}
    return {k: params[v].astype(dtype) for k, v in names.items()}


def logits(params, lm, rows: np.ndarray, dtype=jnp.float32,
           ids=None) -> jax.Array:
    """Logits at every position of one sequence whose embedded rows are
    ``rows`` ``(S, d_model)``: ``(S', vocab)`` on the device, in ``dtype``,
    where ``S'`` pads ``S`` to a multiple of ``PAD`` (the padded rows come
    after the sequence and change none of its logits).  The token ``ids``
    are not needed: the embedding is outside the model."""
    prec = (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = rows.shape[0]
    padded = -(-s // PAD) * PAD
    x = jnp.zeros((padded, rows.shape[1]), dtype).at[:s].set(
        jnp.asarray(rows, dtype))
    for i in range(lm["n_layers"]):
        x = _layer(x, _layer_params(params, i, dtype), lm["n_heads"],
                   lm["n_kv_heads"], prec)
    head = f"{lm['n_layers']}."
    return _head(x, params[head + "weight"].astype(dtype),
                 params[head + "bias"].astype(dtype), prec)
