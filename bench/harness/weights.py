"""The served model's weights, made from ``--seed``.

A model module (``bench/models/<name>.py``) gives its weights as a table
of ``Group``s: name, shape, kind and scale of every weight.  Every weight
is drawn on the device in one jitted call over that table, in float32 (the
type it is served in), under the state-dict names of the framework model
the module builds.  The plain reference draws the same weights with the
same call after the program's state is freed, so it takes nothing that the
program made.  The token embedding is a host table, as the server keeps it,
drawn with numpy.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .spec import seed_words

# Scales of the random weights: a projection's entries have variance
# 1 / fan_in, so activations keep unit scale through each matmul; norm gains
# and all biases are drawn away from 1 and 0 so that a kernel that drops one
# changes the logits.
GAIN_SD = 0.1
NORM_BIAS_SD = 0.1
BIAS_SD = 0.02

# (name, shape, kind, scale); kind "gain" is drawn around 1, "normal"
# around 0; "{i}" in a name stands for the layer's index
Entry = Tuple[str, Tuple[int, ...], str, float]


class Group(NamedTuple):
    """Weights drawn together.  Entry ``j`` is one draw under
    ``fold_in(key, salt + j)``: with ``layers``, a stack of
    ``(len(layers),) + shape`` split into one weight per layer index;
    without, one weight.  Layers of different kinds are groups of their
    own, each with the indices of its kind (``layer_kinds``) and a salt
    that no other group's entries reach."""
    salt: int
    layers: Optional[Tuple[int, ...]]
    entries: Tuple[Entry, ...]


def layer_kinds(n_layers: int, period: Sequence[str],
                leading: Sequence[str] = ()) -> Dict[str, Tuple[int, ...]]:
    """``kind -> layer indices``: the ``leading`` layers first, one of each
    kind named, then ``period`` repeated over the rest."""
    kinds = list(leading) + [period[k % len(period)]
                             for k in range(n_layers - len(leading))]
    return {k: tuple(i for i, x in enumerate(kinds) if x == k)
            for k in dict.fromkeys(kinds)}


def shapes(table: Sequence[Group]) -> Dict[str, Tuple[int, ...]]:
    """``name -> shape`` of every weight."""
    return {name.format(i=i): shape for g in table
            for name, shape, _, _ in g.entries
            for i in (g.layers if g.layers is not None else (None,))}


def make_params(table: Sequence[Group], seed: int, dtype=None) -> Dict:
    """Every weight, on the default device, from one jitted call."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(seed_words(seed, 1)).generate_state(2)
    return _draw(tuple(table), jnp.dtype(dtype or "float32"))(
        jnp.asarray(words, jnp.uint32))


@functools.lru_cache(maxsize=8)
def _draw(table: Tuple[Group, ...], dtype):
    import jax
    import jax.numpy as jnp

    def normal(key, shape, kind, scale):
        x = jax.random.normal(key, shape, jnp.float32) * scale
        return (x + 1.0 if kind == "gain" else x).astype(dtype)

    def draw(words):
        # one draw per entry for all its layers at once, then split: a
        # dozen random ops to compile, not one per weight
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        out = {}
        for g in table:
            for j, (name, shape, kind, scale) in enumerate(g.entries):
                k = jax.random.fold_in(key, g.salt + j)
                if g.layers is None:
                    out[name] = normal(k, shape, kind, scale)
                    continue
                stack = normal(k, (len(g.layers),) + shape, kind, scale)
                out.update({name.format(i=i): stack[r]
                            for r, i in enumerate(g.layers)})
        return out
    return jax.jit(draw)


def embedding(lm: Dict, seed: int) -> np.ndarray:
    """The host token-embedding table, ``(vocab, d_model)`` float32 with
    unit-variance entries: the harness's own, for a model module that
    gives none."""
    rng = np.random.default_rng(seed_words(seed, 2))
    return rng.standard_normal((lm["vocab"], lm["d_model"]), np.float32)
