"""The system under test: a framework model served by ``SolServer``.

The model is built from ``repro.frontends.nn`` modules as a framework user
would write it, then handed unchanged to ``SolServer``, which extracts,
optimizes and serves it (SOL's own premise).
"""
from __future__ import annotations

import gc
from typing import Dict

from . import weights


def build(lm: Dict):
    """Pre-norm blocks (attention with ``n_kv_heads`` KV heads, then a GELU
    MLP ``d_ff`` wide), both residual, and an output head with a bias."""
    from repro.frontends import nn
    d = lm["d_model"]
    blocks = [nn.Sequential(
        nn.Residual(nn.LayerNorm(d),
                    nn.MultiHeadAttention(d, lm["n_heads"], lm["n_kv_heads"])),
        nn.Residual(nn.LayerNorm(d), nn.Linear(d, lm["d_ff"]), nn.GELU(),
                    nn.Linear(lm["d_ff"], d)))
        for _ in range(lm["n_layers"])]
    return nn.Sequential(*blocks, nn.Linear(d, lm["vocab"]))


def load_seeded(model, lm: Dict, seed: int) -> None:
    """Replace the framework's initial weights with the seeded ones.  The
    initial weights are dropped first, so the device never holds two
    copies."""
    import jax.numpy as jnp
    model.load_state_dict({k: jnp.zeros((), jnp.float32)
                           for k in model.state_dict()})
    gc.collect()
    model.load_state_dict(weights.make_params(lm, seed))


def server(lm: Dict, limits: Dict, model, embed, backend: str):
    """A strict-provenance ``SolServer`` for ``model`` under the mix's
    server limits, serving the seeded host embedding."""
    from repro.launch.serve import ServeConfig, SolServer
    cfg = ServeConfig(d_model=lm["d_model"], n_heads=lm["n_heads"],
                      n_layers=lm["n_layers"], vocab=lm["vocab"],
                      max_seq=limits["max_seq"],
                      max_batch=limits["max_batch"], slots=limits["slots"],
                      backend=backend)
    srv = SolServer(cfg, model=model, strict_provenance=True)
    srv.embed = embed
    return srv
