"""Mellum2's decoder: pre-norm blocks of RMSNorm and grouped-query
attention (``n_heads`` query heads of ``head_dim`` over ``n_kv_heads``),
then RMSNorm and a routed-expert layer of SwiGLU experts, both residual; a
final RMSNorm and a bias-free head.  Layers follow ``period``: ``sliding``
layers attend the last ``window`` positions with the default rotary
embedding, ``full`` layers every position with YaRN.  The router scores
all ``n_experts``; the layer holds ``n_held`` of them, from
``first_expert``, and adds their part alone (one chip's share under
expert parallelism).  The token embedding is the harness's host table.

Built from ``repro.frontends.nn`` modules as a framework user would write
it.  Its plain reference is ``bench/references/mellum2_moe_lm.py``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness.weights import GAIN_SD, Group, layer_kinds


def kinds(lm: Dict) -> Dict[str, Tuple[int, ...]]:
    return layer_kinds(lm["n_layers"], lm["period"])


def build(lm: Dict):
    from repro.frontends import nn
    d, hd = lm["d_model"], lm["head_dim"]
    y = lm["yarn"]
    ropes = {"sliding": nn.Rope.default(hd, lm["rope_theta"]),
             "full": nn.Rope.yarn(hd, lm["rope_theta"], y["factor"],
                                  y["original_max_position_embeddings"],
                                  y["beta_fast"], y["beta_slow"],
                                  y["attention_factor"])}
    by_layer = {i: k for k, idx in kinds(lm).items() for i in idx}
    blocks = []
    for i in range(lm["n_layers"]):
        kind = by_layer[i]
        attn = nn.MultiHeadAttention(
            d, lm["n_heads"], lm["n_kv_heads"], head_dim=hd,
            window=lm["window"] if kind == "sliding" else 0,
            rope=ropes[kind])
        experts = nn.MoE(d, lm["d_expert"], lm["n_experts"], lm["top_k"],
                         held=lm["n_held"], first=lm["first_expert"],
                         norm_topk=lm["norm_topk"], score=lm["router"])
        blocks.append(nn.Sequential(
            nn.Residual(nn.RMSNorm(d, lm["rms_eps"]), attn),
            nn.Residual(nn.RMSNorm(d, lm["rms_eps"]), experts)))
    return nn.Sequential(*blocks, nn.RMSNorm(d, lm["rms_eps"]),
                         nn.Linear(d, lm["vocab"], bias=False))


def weights(lm: Dict) -> Tuple[Group, ...]:
    """Block ``i`` is ``i.0`` (``.0`` RMSNorm, ``.1`` attention) and ``i.1``
    (``.0`` RMSNorm, ``.1`` experts); the final norm is ``n_layers``, the
    head ``n_layers + 1``."""
    d, f, n, v = lm["d_model"], lm["d_expert"], lm["n_layers"], lm["vocab"]
    e, held = lm["n_experts"], lm["n_held"]
    q, kv = lm["n_heads"] * lm["head_dim"], lm["n_kv_heads"] * lm["head_dim"]
    block = (("{i}.0.0.weight", (d,), "gain", GAIN_SD),
             ("{i}.0.1.wq", (d, q), "normal", d ** -0.5),
             ("{i}.0.1.wk", (d, kv), "normal", d ** -0.5),
             ("{i}.0.1.wv", (d, kv), "normal", d ** -0.5),
             ("{i}.0.1.wo", (q, d), "normal", q ** -0.5),
             ("{i}.1.0.weight", (d,), "gain", GAIN_SD),
             ("{i}.1.1.router", (d, e), "normal", d ** -0.5),
             ("{i}.1.1.w_gate", (held, d, f), "normal", d ** -0.5),
             ("{i}.1.1.w_up", (held, d, f), "normal", d ** -0.5),
             ("{i}.1.1.w_down", (held, f, d), "normal", f ** -0.5))
    tail = ((f"{n}.weight", (d,), "gain", GAIN_SD),
            (f"{n + 1}.weight", (v, d), "normal", d ** -0.5))
    return (Group(0, tuple(range(n)), block), Group(100, None, tail))


# -- operations the model requires --------------------------------------------

def _attended(lm: Dict, layer: int, pos: int) -> int:
    """Positions that position ``pos`` (from 0) attends in ``layer``."""
    sliding = layer in kinds(lm).get("sliding", ())
    return min(pos + 1, lm["window"]) if sliding else pos + 1


def _token_flops(lm: Dict) -> float:
    """One token through one layer, outside the attention scores: the
    projections, the router, and the experts at the held share of the
    ``top_k`` pairs a token makes on average (``top_k · n_held /
    n_experts``), ``6·d·f`` each."""
    d, f = lm["d_model"], lm["d_expert"]
    q, kv = lm["n_heads"] * lm["head_dim"], lm["n_kv_heads"] * lm["head_dim"]
    pairs = lm["top_k"] * lm["n_held"] / lm["n_experts"]
    return (2.0 * (2 * d * q + 2 * d * kv) + 2.0 * d * lm["n_experts"]
            + 6.0 * d * f * pairs)


def _flops(lm: Dict, positions) -> float:
    attn = sum(4 * lm["n_heads"] * lm["head_dim"] * _attended(lm, i, p)
               for i in range(lm["n_layers"]) for p in positions)
    return float(len(positions) * lm["n_layers"] * _token_flops(lm) + attn
                 + 2 * lm["d_model"] * lm["vocab"])


def prefill_flops(lm: Dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, and the head once, for the last."""
    return _flops(lm, range(prompt))


def decode_flops(lm: Dict, cache: int) -> float:
    """One decoded token after ``cache`` cached positions."""
    return _flops(lm, [cache])


def kernel_work(lm: Dict, op: str, phase: str, batch: int, seq: int
                ) -> List[Tuple[float, float, str]]:
    """``(flops, bytes, weight)`` of every node of kind ``op`` in one
    forward of bucket ``(batch, seq)``: the attention projections
    (``matmul``), the head (``linear``), whose logits cover every prefill
    position, and per ``moe`` node one entry for each of its three expert
    weights, at the expected held pairs of uniform routing, every held
    expert read once."""
    rows = batch * seq if phase == "prefill" else batch
    d, n = lm["d_model"], lm["n_layers"]
    item = np.dtype(lm["dtype"]).itemsize
    q, kv = lm["n_heads"] * lm["head_dim"], lm["n_kv_heads"] * lm["head_dim"]
    if op == "moe":
        f, held = lm["d_expert"], lm["n_held"]
        pairs = rows * lm["top_k"] * held / lm["n_experts"]
        return [(2.0 * pairs * d * f,
                 float(item * (held * d * f + 2 * rows * d / 3)),
                 f"{i}.1.1.{w}") for i in range(n)
                for w in ("w_gate", "w_up", "w_down")]
    if op == "matmul":
        nodes = [(f"{i}.0.1.{w}", k, m) for i in range(n)
                 for w, k, m in (("wq", d, q), ("wk", d, kv), ("wv", d, kv),
                                 ("wo", q, d))]
    elif op == "linear":
        nodes = [(f"{n + 1}.weight", d, lm["vocab"])]
    else:
        return []
    return [(2.0 * rows * k * m, float(item * (rows * k + k * m + rows * m)),
             name) for name, k, m in nodes]
