"""Routing, the sorted pair layout and the plain oracle of the routed-expert
layer (``OpKind.MOE``).

A node holds ``n_held`` of a layer's ``n_experts`` SwiGLU experts, experts
``first .. first + n_held - 1``: the share one chip holds under expert
parallelism.  Every row is routed over all ``n_experts`` (the router's full
width): scores at ``highest`` precision in float32, the ``top_k`` best,
renormalised over them where ``norm_topk``.  The node adds only the held
experts' part, ``sum_e w_e · down_e(silu(gate_e(x)) · up_e(x))`` over the
row's routed experts that are held; the absent experts' part belongs to
the chips that hold them.

Weights: ``router`` (d, n_experts); ``w_gate``, ``w_up`` (n_held, d, f);
``w_down`` (n_held, f, d).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def route(x: jax.Array, router: jax.Array, *, top_k: int,
          norm_topk: bool = True, score: str = "softmax"):
    """x: (T, d), router: (d, E) → (experts (T, k) int32, weights (T, k)
    float32), best first.  A row of zeros (a padding row of a bucket) is
    routed to expert -1, held nowhere: every expert maps it to zero, so it
    adds nothing wherever it goes, and it takes no expert's work."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score != "softmax":
        raise ValueError(f"unknown router score function {score!r}")
    w, e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        w = w / w.sum(-1, keepdims=True)
    live = jnp.any(x != 0, axis=-1, keepdims=True)
    return jnp.where(live, e, -1).astype(jnp.int32), w


def held_counts(experts: jax.Array, first: int, n_held: int) -> jax.Array:
    """int32 ``(pairs, touched)``: the (row, expert) pairs routed to a held
    expert, and how many held experts received any."""
    local = experts.reshape(-1) - first
    per = (local[:, None] == jnp.arange(n_held)[None, :]).sum(0)
    return jnp.stack([per.sum(), (per > 0).sum()]).astype(jnp.int32)


class Layout(NamedTuple):
    """Where each (row, expert) pair of ``experts`` (flattened, row-major)
    sits in the sorted layout: held experts' pairs contiguous in expert
    order, each expert's group starting at a multiple of ``align``."""
    held: jax.Array       # (P,) bool: routed to a held expert
    pos: jax.Array        # (P,) int32 slot in the layout (capacity if not)
    src: jax.Array        # (capacity,) int32 row of each slot (T if none)
    sizes: jax.Array      # (n_held,) int32 pairs per held expert
    padded: jax.Array     # (n_held,) int32 sizes rounded up to ``align``


def capacity(rows: int, top_k: int, n_held: int, align: int) -> int:
    """Slots of the sorted layout: every pair a row can route here, plus
    each group's rounding up to ``align``."""
    most = rows * min(top_k, n_held) + n_held * (align - 1)
    return -(-most // align) * align


def layout(experts: jax.Array, first: int, n_held: int,
           align: int = 1) -> Layout:
    rows, k = experts.shape
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    onehot = (local[:, None] == jnp.arange(n_held)[None, :]).astype(jnp.int32)
    sizes = onehot.sum(0)
    rank = ((jnp.cumsum(onehot, 0) - onehot) * onehot).sum(1)
    padded = -(-sizes // align) * align
    starts = jnp.cumsum(padded) - padded
    cap = capacity(rows, k, n_held, align)
    pos = jnp.where(held, (onehot * starts).sum(1) + rank, cap)
    src = jnp.full((cap,), rows, jnp.int32).at[pos].set(
        jnp.arange(rows * k, dtype=jnp.int32) // k, mode="drop")
    return Layout(held, pos.astype(jnp.int32), src, sizes, padded)


def gather_rows(x: jax.Array, lay: Layout) -> jax.Array:
    """x: (T, d) → the layout's rows (capacity, d), zeros in empty slots."""
    rows = x.shape[0]
    return jnp.where((lay.src < rows)[:, None],
                     x[jnp.minimum(lay.src, rows - 1)], 0).astype(x.dtype)


def combine(y: jax.Array, lay: Layout, weights: jax.Array) -> jax.Array:
    """The layout's outputs (capacity, d) → each row's weighted sum over its
    held experts (T, d), in float32."""
    rows, k = weights.shape
    got = jnp.where(lay.held[:, None],
                    y[jnp.minimum(lay.pos, y.shape[0] - 1)], 0)
    return jnp.einsum("tkd,tk->td", got.reshape(rows, k, -1)
                      .astype(jnp.float32), weights,
                      precision=jax.lax.Precision.HIGHEST)


def moe_ref(x, router, w_gate, w_up, w_down, *, top_k: int, first: int = 0,
            norm_topk: bool = True, score: str = "softmax") -> jax.Array:
    """The XLA lowering: route, sort the held pairs by expert and run only
    those through ``jax.lax.ragged_dot`` (a grouped matmul on the TPU).
    x: (T, d) → (T, d)."""
    n_held = w_gate.shape[0]
    experts, weights = route(x, router, top_k=top_k, norm_topk=norm_topk,
                             score=score)
    lay = layout(experts, first, n_held)
    xs = gather_rows(x, lay)
    dot = jax.lax.ragged_dot
    f32 = jnp.float32
    g = dot(xs, w_gate, lay.sizes, preferred_element_type=f32)
    u = dot(xs, w_up, lay.sizes, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = dot(h, w_down, lay.sizes, preferred_element_type=f32)
    return combine(y, lay, weights).astype(x.dtype)


def moe_dense(x, router, w_gate, w_up, w_down, *, top_k: int, first: int = 0,
              norm_topk: bool = True, score: str = "softmax") -> jax.Array:
    """Oracle: every held expert over every row, weighted by the row's
    routing weight for it (0 where it was not routed there)."""
    n_held = w_gate.shape[0]
    experts, weights = route(x, router, top_k=top_k, norm_topk=norm_topk,
                             score=score)
    mine = experts - first
    gate = ((mine[..., None] == jnp.arange(n_held)) * weights[..., None]
            ).sum(1)                                           # (T, n_held)
    f32 = jnp.float32
    g = jnp.einsum("td,edf->etf", x, w_gate, preferred_element_type=f32)
    u = jnp.einsum("td,edf->etf", x, w_up, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, w_down, preferred_element_type=f32)
    return jnp.einsum("te,etd->td", gate, y).astype(x.dtype)
