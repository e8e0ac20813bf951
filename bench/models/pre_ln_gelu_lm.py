"""Pre-norm LM: blocks of LayerNorm and multi-head attention (``n_kv_heads``
KV heads), then LayerNorm and a GELU MLP ``d_ff`` wide, both residual; an
output head with a bias.  The token embedding is the harness's host table.

The model is built from ``repro.frontends.nn`` modules as a framework user
would write it.  Its plain reference is ``bench/references/pre_ln_gelu_lm.py``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness.weights import BIAS_SD, GAIN_SD, NORM_BIAS_SD, Group


def build(lm: Dict):
    from repro.frontends import nn
    d = lm["d_model"]
    blocks = [nn.Sequential(
        nn.Residual(nn.LayerNorm(d),
                    nn.MultiHeadAttention(d, lm["n_heads"], lm["n_kv_heads"])),
        nn.Residual(nn.LayerNorm(d), nn.Linear(d, lm["d_ff"]), nn.GELU(),
                    nn.Linear(lm["d_ff"], d)))
        for _ in range(lm["n_layers"])]
    return nn.Sequential(*blocks, nn.Linear(d, lm["vocab"]))


def weights(lm: Dict) -> Tuple[Group, ...]:
    """Every weight in the framework model's state-dict naming: block ``i``
    is ``i.0`` (attention residual: ``.0`` LayerNorm, ``.1`` attention) and
    ``i.1`` (MLP residual: ``.0`` LayerNorm, ``.1`` and ``.3`` Linear); the
    head is block ``n_layers``."""
    d, f, n, v = lm["d_model"], lm["d_ff"], lm["n_layers"], lm["vocab"]
    hd = d // lm["n_heads"]
    q, kv = lm["n_heads"] * hd, lm["n_kv_heads"] * hd
    block = (("{i}.0.0.weight", (d,), "gain", GAIN_SD),
             ("{i}.0.0.bias", (d,), "normal", NORM_BIAS_SD),
             ("{i}.0.1.wq", (d, q), "normal", d ** -0.5),
             ("{i}.0.1.wk", (d, kv), "normal", d ** -0.5),
             ("{i}.0.1.wv", (d, kv), "normal", d ** -0.5),
             ("{i}.0.1.wo", (q, d), "normal", q ** -0.5),
             ("{i}.1.0.weight", (d,), "gain", GAIN_SD),
             ("{i}.1.0.bias", (d,), "normal", NORM_BIAS_SD),
             ("{i}.1.1.weight", (f, d), "normal", d ** -0.5),
             ("{i}.1.1.bias", (f,), "normal", BIAS_SD),
             ("{i}.1.3.weight", (d, f), "normal", f ** -0.5),
             ("{i}.1.3.bias", (d,), "normal", BIAS_SD))
    head = ((f"{n}.weight", (v, d), "normal", d ** -0.5),
            (f"{n}.bias", (v,), "normal", BIAS_SD))
    return (Group(0, tuple(range(n)), block), Group(100, None, head))


# -- operations the model requires --------------------------------------------

def _layer_flops(lm: Dict, tokens: int) -> float:
    d, f, h, kv = (lm["d_model"], lm["d_ff"], lm["n_heads"],
                   lm["n_kv_heads"])
    hd = d // h
    return 2.0 * tokens * (d * h * hd + 2 * d * kv * hd + h * hd * d
                           + 2 * d * f)


def prefill_flops(lm: Dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens: every layer's projections and MLP per
    token, causal attention over ``prompt·(prompt+1)/2`` pairs (scores and
    the weighted sum, ``4·hd`` operations a pair and head), and the head
    once, for the last position."""
    h, hd = lm["n_heads"], lm["d_model"] // lm["n_heads"]
    attn = 4.0 * h * hd * prompt * (prompt + 1) / 2
    return (lm["n_layers"] * (_layer_flops(lm, prompt) + attn)
            + 2.0 * lm["d_model"] * lm["vocab"])


def decode_flops(lm: Dict, cache: int) -> float:
    """One decoded token attending ``cache`` cached positions and itself."""
    h, hd = lm["n_heads"], lm["d_model"] // lm["n_heads"]
    attn = 4.0 * h * hd * (cache + 1)
    return (lm["n_layers"] * (_layer_flops(lm, 1) + attn)
            + 2.0 * lm["d_model"] * lm["vocab"])


def kernel_work(lm: Dict, op: str, phase: str, batch: int, seq: int
                ) -> List[Tuple[float, float, str]]:
    """``(flops, bytes, weight)`` of every node of kind ``op`` in one
    forward of bucket ``(batch, seq)``, with the state-dict name of the
    weight it reads: a prefill runs ``batch·seq`` rows through every
    projection and the head (its logits cover every position), a decode
    ``batch`` rows.  Bytes read the input rows and the weight once and
    write the output once."""
    rows = batch * seq if phase == "prefill" else batch
    d, f, n = lm["d_model"], lm["d_ff"], lm["n_layers"]
    hd = d // lm["n_heads"]
    q, kv = lm["n_heads"] * hd, lm["n_kv_heads"] * hd
    if op == "matmul":          # the attention projections, (in, out)
        nodes = [(f"{i}.0.1.{w}", k, m) for i in range(n)
                 for w, k, m in (("wq", d, q), ("wk", d, kv), ("wv", d, kv),
                                 ("wo", q, d))]
    elif op == "linear":        # the MLP and the head
        nodes = [(f"{i}.1.{j}.weight", k, m) for i in range(n)
                 for j, k, m in ((1, d, f), (3, f, d))]
        nodes.append((f"{n}.weight", d, lm["vocab"]))
    else:
        return []
    item = np.dtype(lm["dtype"]).itemsize
    return [(2.0 * rows * k * m, float(item * (rows * k + k * m + rows * m)),
             name) for name, k, m in nodes]
