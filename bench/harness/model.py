"""The system under test: a framework model served by ``SolServer``.

The model module a configuration names (``bench/models/<name>.py``) builds
the model from ``repro.frontends.nn`` modules as a framework user would
write it; the harness seeds its weights and hands it unchanged to
``SolServer``, which extracts, optimizes and serves it (SOL's own premise).
A module may give its own ``embedding(lm, seed)`` and ``server(lm, limits,
model, embed, backend)``; the harness's are used where it gives none.
"""
from __future__ import annotations

import gc
from typing import Dict

from . import weights


def build_seeded(arch, lm: Dict, seed: int):
    """The module's framework model with the seeded weights in place of
    its initial ones.  The initial weights are dropped first, so the
    device never holds two copies."""
    model = arch.build(lm)
    load_seeded(model, arch.weights(lm), seed)
    return model


def load_seeded(model, table, seed: int) -> None:
    """Load the weights ``table`` draws from ``seed`` into ``model``."""
    import jax.numpy as jnp
    model.load_state_dict({k: jnp.zeros((), jnp.float32)
                           for k in model.state_dict()})
    gc.collect()
    model.load_state_dict(weights.make_params(table, seed))


def embedding(arch, lm: Dict, seed: int):
    """The token-embedding table the server is handed."""
    return getattr(arch, "embedding", weights.embedding)(lm, seed)


def server(arch, lm: Dict, limits: Dict, model, embed, backend: str):
    """The module's server, or a strict-provenance ``SolServer`` for
    ``model`` under the mix's server limits, serving ``embed``."""
    if hasattr(arch, "server"):
        return arch.server(lm, limits, model, embed, backend)
    from repro.launch.serve import ServeConfig, SolServer
    cfg = ServeConfig(d_model=lm["d_model"], n_heads=lm["n_heads"],
                      n_layers=lm["n_layers"], vocab=lm["vocab"],
                      max_seq=limits["max_seq"],
                      max_batch=limits["max_batch"], slots=limits["slots"],
                      backend=backend)
    srv = SolServer(cfg, model=model, strict_provenance=True)
    srv.embed = embed
    return srv
