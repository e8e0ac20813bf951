"""Operations the served model requires, computed from the
configuration's widths: ``prefill_flops`` and ``decode_flops`` count the
real tokens alone (no padding, the head once per prompt and once per
decoded token, causal attention once), the numerator of ``mfu.serve``.
"""
from __future__ import annotations

from typing import Dict


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
