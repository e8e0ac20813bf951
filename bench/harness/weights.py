"""The served model's weights, made from ``--seed``.

Every weight of the model is drawn on the device in one jitted call, in
float32 (the type it is served in), under the state-dict names of the
framework model that ``model.build`` makes.  The plain reference draws the
same weights with the same call after the program's state is freed, so it
takes nothing that the program made.  The token embedding is a host table,
as the server keeps it, drawn with numpy.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

from .spec import seed_words

# Scales of the random weights: a projection's entries have variance
# 1 / fan_in, so activations keep unit scale through each matmul; norm gains
# and all biases are drawn away from 1 and 0 so that a kernel that drops one
# changes the logits.
GAIN_SD = 0.1
NORM_BIAS_SD = 0.1
BIAS_SD = 0.02


def _layer_table(lm: Dict):
    """``(suffix, shape, kind, scale)`` of every weight of one block, in the
    framework model's state-dict naming: block ``i`` is ``i.0`` (attention
    residual: ``.0`` LayerNorm, ``.1`` attention) and ``i.1`` (MLP residual:
    ``.0`` LayerNorm, ``.1`` and ``.3`` Linear)."""
    d, f = lm["d_model"], lm["d_ff"]
    hd = d // lm["n_heads"]
    q, kv = lm["n_heads"] * hd, lm["n_kv_heads"] * hd
    return [("0.0.weight", (d,), "gain", GAIN_SD),
            ("0.0.bias", (d,), "normal", NORM_BIAS_SD),
            ("0.1.wq", (d, q), "normal", d ** -0.5),
            ("0.1.wk", (d, kv), "normal", d ** -0.5),
            ("0.1.wv", (d, kv), "normal", d ** -0.5),
            ("0.1.wo", (q, d), "normal", q ** -0.5),
            ("1.0.weight", (d,), "gain", GAIN_SD),
            ("1.0.bias", (d,), "normal", NORM_BIAS_SD),
            ("1.1.weight", (f, d), "normal", d ** -0.5),
            ("1.1.bias", (f,), "normal", BIAS_SD),
            ("1.3.weight", (d, f), "normal", f ** -0.5),
            ("1.3.bias", (d,), "normal", BIAS_SD)]


def _head_table(lm: Dict):
    d, v = lm["d_model"], lm["vocab"]
    return [("weight", (v, d), "normal", d ** -0.5),
            ("bias", (v,), "normal", BIAS_SD)]


def shapes(lm: Dict) -> Dict[str, Tuple[int, ...]]:
    """``name -> shape`` of every weight; the head is block ``n_layers``."""
    out = {f"{i}.{sfx}": shape for i in range(lm["n_layers"])
           for sfx, shape, _, _ in _layer_table(lm)}
    out.update({f"{lm['n_layers']}.{sfx}": shape
                for sfx, shape, _, _ in _head_table(lm)})
    return out


def make_params(lm: Dict, seed: int, dtype=None) -> Dict:
    """Every weight, on the default device, from one jitted call."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(seed_words(seed, 1)).generate_state(2)
    key = tuple(sorted((k, v) for k, v in lm.items()
                       if isinstance(v, int)))
    return _draw(key, jnp.dtype(dtype or "float32"))(
        jnp.asarray(words, jnp.uint32))


@functools.lru_cache(maxsize=8)
def _draw(lm_key, dtype):
    import jax
    import jax.numpy as jnp
    lm = dict(lm_key)
    n = lm["n_layers"]

    def normal(key, shape, kind, scale):
        x = jax.random.normal(key, shape, jnp.float32) * scale
        return (x + 1.0 if kind == "gain" else x).astype(dtype)

    def draw(words):
        # one draw per kind of weight for all blocks at once, then split:
        # a dozen random ops to compile, not one per weight
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        out = {}
        for j, (sfx, shape, kind, scale) in enumerate(_layer_table(lm)):
            stack = normal(jax.random.fold_in(key, j), (n,) + shape, kind,
                           scale)
            out.update({f"{i}.{sfx}": stack[i] for i in range(n)})
        for j, (sfx, shape, kind, scale) in enumerate(_head_table(lm)):
            out[f"{n}.{sfx}"] = normal(jax.random.fold_in(key, 100 + j),
                                       shape, kind, scale)
        return out
    return jax.jit(draw)


def embedding(lm: Dict, seed: int) -> np.ndarray:
    """The host token-embedding table, ``(vocab, d_model)`` float32 with
    unit-variance entries."""
    rng = np.random.default_rng(seed_words(seed, 2))
    return rng.standard_normal((lm["vocab"], lm["d_model"]), np.float32)
