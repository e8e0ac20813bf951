"""Grouped matmul of the routed-expert layer (TPU Pallas).

The (row, expert) pairs a node holds arrive sorted by expert, each
expert's group padded to a multiple of the row tile ``tm``
(``ref.layout``), so every row tile belongs to one expert.  Two scalar
operands are prefetched to SMEM: ``tile_expert``, the expert of each row
tile, and ``n_live``, how many row tiles hold any pair.  The weight
operand's index map reads the tile's expert, so a step fetches that
expert's weight block; tiles past ``n_live`` map every operand to the
last live tile's blocks, which Pallas does not fetch again, and compute
nothing.  An expert that no pair was routed to owns no tile: its weights
are never read, so a decode step reads the touched experts alone.

Two calls per layer:

- ``gate_up``: grid (row tiles, f / tf, d / td), d innermost; two f32
  accumulators (gate and up) carried over d, and ``silu(gate) · up`` stored
  at the last d step;
- ``down``: grid (row tiles, d / td, f / tf), f innermost; one accumulator.

Blocks: rows (tm, td) or (tm, tf); weights (1, td, tf) or (1, tf, td).
``tf`` and ``td`` divide f and d and are multiples of 128, or the whole
width; :func:`pick_blocks` takes the widest that fit the VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._util import VMEM_LIMIT_BYTES, tpu_params


def _splits(width: int):
    """Block widths for one dimension, widest first: the whole width, then
    each multiple of 128 that divides it."""
    return [width] + [b for b in range(width - 128, 0, -128)
                      if width % b == 0 and b % 128 == 0]


def vmem_bytes(tm: int, tf: int, td: int, itemsize: int) -> int:
    """Working set of ``gate_up``, the larger call: double-buffered row,
    two weight and output blocks, and two f32 accumulators."""
    return (2 * itemsize * (tm * td + 2 * td * tf + tm * tf)
            + 2 * 4 * tm * tf)


def pick_blocks(tm: int, d: int, f: int, itemsize: int) -> Tuple[int, int]:
    """(tf, td): the widest blocks whose working set fits the budget,
    preferring whole rows of the weights (the whole ``f``)."""
    for tf in _splits(f):
        for td in _splits(d):
            if vmem_bytes(tm, tf, td, itemsize) <= VMEM_LIMIT_BYTES:
                return tf, td
    return _splits(f)[-1], _splits(d)[-1]


def _live_maps(nk: int, nj: int):
    """Index maps of (rows, weights, output) for grid (i, j, k): tiles
    past ``n_live`` keep the last live tile's blocks."""
    def tile(i, live):
        return jnp.minimum(i, jnp.maximum(live[0], 1) - 1)

    def kk(i, k, live):
        return jnp.where(i < live[0], k, nk - 1)

    def jj(i, j, live):
        return jnp.where(i < live[0], j, nj - 1)

    def rows(i, j, k, te, live):
        return tile(i, live), kk(i, k, live)

    def weight(i, j, k, te, live):
        return te[tile(i, live)], kk(i, k, live), jj(i, j, live)

    def out(i, j, k, te, live):
        return tile(i, live), jj(i, j, live)
    return rows, weight, out


def _gate_up_kernel(nk, te_ref, live_ref, x_ref, wg_ref, wu_ref, h_ref,
                    acc_g, acc_u):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    @pl.when(i < live_ref[0])
    def _acc():
        x = x_ref[...]
        acc_g[...] += jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        acc_u[...] += jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)

    @pl.when((k == nk - 1) & (i < jnp.maximum(live_ref[0], 1)))
    def _store():
        g = acc_g[...]
        h_ref[...] = (g * jax.nn.sigmoid(g) * acc_u[...]).astype(h_ref.dtype)


def _down_kernel(nk, te_ref, live_ref, h_ref, wd_ref, y_ref, acc):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(i < live_ref[0])
    def _acc():
        acc[...] += jnp.dot(h_ref[...], wd_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when((k == nk - 1) & (i < jnp.maximum(live_ref[0], 1)))
    def _store():
        y_ref[...] = acc[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tf", "td", "interpret"))
def expert_gmm(xs, tile_expert, n_live, w_gate, w_up, w_down, *, tm: int,
               tf: int, td: int, interpret: bool = False) -> jax.Array:
    """xs: (M, d) rows sorted by expert in ``tm``-row tiles of one expert
    each; tile_expert: (M // tm,) int32; n_live: (1,) int32 →
    ``down(silu(gate(x)) · up(x))`` per row, (M, d)."""
    m, d = xs.shape
    f = w_gate.shape[-1]
    nt, nf, nd = m // tm, f // tf, d // td
    rows, weight, out = _live_maps(nd, nf)
    h = pl.pallas_call(
        functools.partial(_gate_up_kernel, nd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nt, nf, nd),
            in_specs=[pl.BlockSpec((tm, td), rows),
                      pl.BlockSpec((1, td, tf), weight),
                      pl.BlockSpec((1, td, tf), weight)],
            out_specs=pl.BlockSpec((tm, tf), out),
            scratch_shapes=[pltpu.VMEM((tm, tf), jnp.float32),
                            pltpu.VMEM((tm, tf), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, f), xs.dtype),
        compiler_params=tpu_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(tile_expert, n_live, xs, w_gate, w_up)
    rows, weight, out = _live_maps(nf, nd)
    return pl.pallas_call(
        functools.partial(_down_kernel, nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nt, nd, nf),
            in_specs=[pl.BlockSpec((tm, tf), rows),
                      pl.BlockSpec((1, tf, td), weight)],
            out_specs=pl.BlockSpec((tm, td), out),
            scratch_shapes=[pltpu.VMEM((tm, td), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        compiler_params=tpu_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(tile_expert, n_live, h, w_down)
