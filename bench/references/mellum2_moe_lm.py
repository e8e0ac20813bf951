"""Plain reference of ``mellum2_moe_lm`` (Mellum2's decoder, one chip's
expert share): pre-norm blocks of RMSNorm, grouped-query attention and
routed SwiGLU experts, both residual, then RMSNorm and a bias-free head.

- Attention: query head ``h`` reads KV head ``h // (n_heads //
  n_kv_heads)``, causal, scaled by ``head_dim ** -0.5``; ``sliding``
  layers see the last ``window`` positions.  q and k are rotated
  (``rotate_half``) at their positions: by the default frequencies
  ``theta ** (-2i / head_dim)`` on sliding layers, by YaRN's on full ones
  (frequencies below ``beta_slow`` turns in the original context divided
  by ``factor``, above ``beta_fast`` kept, a linear ramp between; cos and
  sin scaled by ``attention_factor``).
- Experts: the router's softmax over all ``n_experts``, the ``top_k``
  best renormalised; each held expert (``first_expert`` on, ``n_held`` of
  them) adds ``w · down(silu(gate(x)) · up(x))`` where it was routed, and
  the others add nothing, as on the chip that holds this share.

Straight ``jax.numpy``, one sequence at a time, imported from nothing of
the program.  In float32 every matmul, the router's included, runs at
``highest`` precision; with ``dtype=bfloat16`` (the control) weights,
activations and every intermediate are bfloat16 at default precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512                # sequence lengths are padded to a multiple of this


def _kinds(lm):
    period = list(lm["period"])
    return [period[i % len(period)] for i in range(lm["n_layers"])]


def _inv_freq(lm, kind) -> np.ndarray:
    hd, theta = lm["head_dim"], lm["rope_theta"]
    base = theta ** (np.arange(0, hd, 2) / hd)
    if kind == "sliding":
        return 1.0 / base, 1.0
    y = lm["yarn"]

    def turns_dim(turns):
        return (hd * math.log(y["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta)))
    lo = max(math.floor(turns_dim(y["beta_fast"])), 0)
    hi = min(math.ceil(turns_dim(y["beta_slow"])), hd - 1)
    keep = 1.0 - np.clip((np.arange(hd // 2) - lo) / max(hi - lo, 1e-3),
                         0.0, 1.0)
    inv = (1.0 / (y["factor"] * base)) * (1.0 - keep) + (1.0 / base) * keep
    return inv, y["attention_factor"]


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


@functools.partial(jax.jit, static_argnames=("lm_t", "prec"))
def _layer(x, p, cos, sin, window, lm_t, prec):
    lm = dict(lm_t)
    s = x.shape[0]
    h, kvh, hd = lm["n_heads"], lm["n_kv_heads"], lm["head_dim"]
    mm = functools.partial(jnp.matmul, precision=prec)
    t = _rms(x, p["ln1"], lm["rms_eps"])
    q = mm(t, p["wq"]).reshape(s, kvh, h // kvh, hd)
    k = mm(t, p["wk"]).reshape(s, kvh, hd)
    v = mm(t, p["wv"]).reshape(s, kvh, hd)
    q = _rotate(q, cos[:, None, None, :], sin[:, None, None, :])
    k = _rotate(k, cos[:, None, :], sin[:, None, :])
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = (kp <= qp) & ((window == 0) | (qp - kp < window))
    sc = jnp.einsum("qkgd,skd->kgqs", q, k, precision=prec) \
        * jnp.asarray(hd ** -0.5, x.dtype)
    pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", pr.astype(x.dtype), v,
                   precision=prec).reshape(s, h * hd)
    x = x + mm(o, p["wo"])

    t = _rms(x, p["ln2"], lm["rms_eps"])
    probs = jax.nn.softmax(mm(t, p["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, lm["top_k"])
    if lm["norm_topk"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    held = jnp.arange(lm["n_held"]) + lm["first_expert"]
    w = ((top_e[..., None] == held) * top_w[..., None]).sum(1)  # (s, held)
    g = jnp.einsum("sd,edf->esf", t, p["w_gate"], precision=prec)
    u = jnp.einsum("sd,edf->esf", t, p["w_up"], precision=prec)
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, p["w_down"],
                   precision=prec)
    return x + jnp.einsum("se,esd->sd", w.astype(x.dtype), y, precision=prec)


def _layer_params(params, i, dtype):
    a, m = f"{i}.0.", f"{i}.1."
    names = {"ln1": a + "0.weight", "wq": a + "1.wq", "wk": a + "1.wk",
             "wv": a + "1.wv", "wo": a + "1.wo", "ln2": m + "0.weight",
             "router": m + "1.router", "w_gate": m + "1.w_gate",
             "w_up": m + "1.w_up", "w_down": m + "1.w_down"}
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
    keep = ("n_heads", "n_kv_heads", "head_dim", "rms_eps", "top_k",
            "norm_topk", "n_held", "first_expert")
    lm_t = tuple((k, lm[k]) for k in keep)
    pos = np.arange(padded, dtype=np.float64)
    for i, kind in enumerate(_kinds(lm)):
        inv, scale = _inv_freq(lm, kind)
        ang = np.concatenate([np.outer(pos, inv)] * 2, -1)
        cos = jnp.asarray(np.cos(ang) * scale, dtype)
        sin = jnp.asarray(np.sin(ang) * scale, dtype)
        window = lm["window"] if kind == "sliding" else 0
        x = _layer(x, _layer_params(params, i, dtype), cos, sin, window,
                   lm_t, prec)
    n = lm["n_layers"]
    x = _rms(x, params[f"{n}.weight"].astype(dtype), lm["rms_eps"])
    return jnp.matmul(x, params[f"{n + 1}.weight"].astype(dtype).T,
                      precision=prec)
