"""A second architecture for the benchmark's tests, brought as files alone:
one leading dense layer, then layers that alternate local (windowed) and
global attention.  Every attention runs at ``head_dim`` apart from
``d_model // n_heads``: a bias-free projection from ``d_model`` to
``n_heads · head_dim`` before it and back after it.  Dense layers have a
GELU MLP, the others a ReLU MLP.  The module gives its own embedding (the
harness's, halved)."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness.weights import (BIAS_SD, GAIN_SD, NORM_BIAS_SD, Group,
                             embedding as host_embedding, layer_kinds)

SALT = {"dense": 0, "local": 100, "global": 200}


def kinds(lm: Dict) -> Dict[str, Tuple[int, ...]]:
    return layer_kinds(lm["n_layers"], lm["period"], lm["leading"])


def build(lm: Dict):
    from repro.frontends import nn
    d, h, hd, f = lm["d_model"], lm["n_heads"], lm["head_dim"], lm["d_ff"]
    by_layer = {i: k for k, idx in kinds(lm).items() for i in idx}
    blocks = []
    for i in range(lm["n_layers"]):
        kind = by_layer[i]
        window = lm["window"] if kind == "local" else 0
        act = nn.GELU() if kind == "dense" else nn.ReLU()
        blocks.append(nn.Sequential(
            nn.Residual(nn.LayerNorm(d), nn.Linear(d, h * hd, bias=False),
                        nn.MultiHeadAttention(h * hd, h, lm["n_kv_heads"],
                                              window=window),
                        nn.Linear(h * hd, d, bias=False)),
            nn.Residual(nn.LayerNorm(d), nn.Linear(d, f), act,
                        nn.Linear(f, d))))
    return nn.Sequential(*blocks, nn.Linear(d, lm["vocab"]))


def weights(lm: Dict) -> Tuple[Group, ...]:
    d, f, n, v = lm["d_model"], lm["d_ff"], lm["n_layers"], lm["vocab"]
    a = lm["n_heads"] * lm["head_dim"]
    kv = lm["n_kv_heads"] * lm["head_dim"]
    block = (("{i}.0.0.weight", (d,), "gain", GAIN_SD),
             ("{i}.0.0.bias", (d,), "normal", NORM_BIAS_SD),
             ("{i}.0.1.weight", (a, d), "normal", d ** -0.5),
             ("{i}.0.2.wq", (a, a), "normal", a ** -0.5),
             ("{i}.0.2.wk", (a, kv), "normal", a ** -0.5),
             ("{i}.0.2.wv", (a, kv), "normal", a ** -0.5),
             ("{i}.0.2.wo", (a, a), "normal", a ** -0.5),
             ("{i}.0.3.weight", (d, a), "normal", a ** -0.5),
             ("{i}.1.0.weight", (d,), "gain", GAIN_SD),
             ("{i}.1.0.bias", (d,), "normal", NORM_BIAS_SD),
             ("{i}.1.1.weight", (f, d), "normal", d ** -0.5),
             ("{i}.1.1.bias", (f,), "normal", BIAS_SD),
             ("{i}.1.3.weight", (d, f), "normal", f ** -0.5),
             ("{i}.1.3.bias", (d,), "normal", BIAS_SD))
    groups = tuple(Group(SALT[k], idx, block) for k, idx in kinds(lm).items())
    head = ((f"{n}.weight", (v, d), "normal", d ** -0.5),
            (f"{n}.bias", (v,), "normal", BIAS_SD))
    return groups + (Group(1000, None, head),)


def embedding(lm: Dict, seed: int) -> np.ndarray:
    return 0.5 * host_embedding(lm, seed)


def _shapes(lm: Dict, i: int) -> Dict[str, List[Tuple[str, int, int]]]:
    """``(weight, in, out)`` of every projection of layer ``i``, by node
    kind."""
    d, f = lm["d_model"], lm["d_ff"]
    a = lm["n_heads"] * lm["head_dim"]
    kv = lm["n_kv_heads"] * lm["head_dim"]
    return {"linear": [(f"{i}.0.1.weight", d, a), (f"{i}.0.3.weight", a, d),
                       (f"{i}.1.1.weight", d, f), (f"{i}.1.3.weight", f, d)],
            "matmul": [(f"{i}.0.2.wq", a, a), (f"{i}.0.2.wk", a, kv),
                       (f"{i}.0.2.wv", a, kv), (f"{i}.0.2.wo", a, a)]}


def _attended(lm: Dict, i: int, pos: int) -> int:
    """Positions that position ``pos`` (from 0) of layer ``i`` attends."""
    local = i in kinds(lm).get("local", ())
    return min(pos + 1, lm["window"]) if local else pos + 1


def _flops(lm: Dict, positions) -> float:
    per = sum(2 * k * m for _, k, m in sum(_shapes(lm, 0).values(), []))
    attn = sum(4 * lm["n_heads"] * lm["head_dim"] * _attended(lm, i, p)
               for i in range(lm["n_layers"]) for p in positions)
    return float(len(positions) * lm["n_layers"] * per + attn
                 + 2 * lm["d_model"] * lm["vocab"])


def prefill_flops(lm: Dict, prompt: int) -> float:
    return _flops(lm, range(prompt))


def decode_flops(lm: Dict, cache: int) -> float:
    return _flops(lm, [cache])


def kernel_work(lm: Dict, op: str, phase: str, batch: int, seq: int
                ) -> List[Tuple[float, float, str]]:
    rows = batch * seq if phase == "prefill" else batch
    nodes = [x for i in range(lm["n_layers"])
             for x in _shapes(lm, i).get(op, [])]
    if op == "linear":
        nodes.append((f"{lm['n_layers']}.weight", lm["d_model"], lm["vocab"]))
    return [(2.0 * rows * k * m, 4.0 * (rows * k + k * m + rows * m), w)
            for w, k, m in nodes]
