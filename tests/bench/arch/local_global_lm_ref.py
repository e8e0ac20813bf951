"""Plain reference of ``local_global_lm`` (the benchmark's tests' second
architecture): pre-norm blocks of attention at ``head_dim`` between
bias-free projections (grouped KV heads, causal, local layers seeing the
last ``window`` positions) and an MLP (GELU in the dense layer, ReLU in the
others), then an output head with a bias.  Straight ``jax.numpy``, one
sequence at a time, ``highest`` precision in float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _kinds(lm):
    lead, period = list(lm["leading"]), list(lm["period"])
    return lead + [period[k % len(period)]
                   for k in range(lm["n_layers"] - len(lead))]


def logits(params, lm, rows: np.ndarray, dtype=jnp.float32, ids=None):
    assert ids is not None and len(ids) == len(rows)
    prec = (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def mm(a, b):
        return jnp.matmul(a, b, precision=prec)

    def p(name):
        return params[name].astype(dtype)

    h, kvh, hd = lm["n_heads"], lm["n_kv_heads"], lm["head_dim"]
    s = rows.shape[0]
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    x = jnp.asarray(rows, dtype)
    for i, kind in enumerate(_kinds(lm)):
        a, m = f"{i}.0.", f"{i}.1."
        mask = kp <= qp
        if kind == "local":
            mask &= qp - kp < lm["window"]
        t = mm(_ln(x, p(a + "0.weight"), p(a + "0.bias")),
               p(a + "1.weight").T)
        q = mm(t, p(a + "2.wq")).reshape(s, kvh, h // kvh, hd)
        k = mm(t, p(a + "2.wk")).reshape(s, kvh, hd)
        v = mm(t, p(a + "2.wv")).reshape(s, kvh, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", q, k, precision=prec) \
            * jnp.asarray(hd ** -0.5, dtype)
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", pr.astype(dtype), v,
                       precision=prec).reshape(s, h * hd)
        x = x + mm(mm(o, p(a + "2.wo")), p(a + "3.weight").T)
        t = _ln(x, p(m + "0.weight"), p(m + "0.bias"))
        t = mm(t, p(m + "1.weight").T) + p(m + "1.bias")
        t = jax.nn.gelu(t) if kind == "dense" else jax.nn.relu(t)
        x = x + mm(t, p(m + "3.weight").T) + p(m + "3.bias")
    n = lm["n_layers"]
    return mm(x, p(f"{n}.weight").T) + p(f"{n}.bias")
