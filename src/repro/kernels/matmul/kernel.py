"""Tiled MXU matmul (TPU Pallas) — the DNN-module flavour the ROADMAP named:
``pallas_tpu`` advertises the 'mxu' capability, this is the kernel that uses
it for LINEAR/MATMUL instead of lowering through the reference einsum.

Grid: (M/bm, N/bn, K/bk) with the K dimension innermost.  Each (i, j) output
tile owns an f32 VMEM scratch accumulator that carries across the K steps:
zeroed at k == 0, one ``jnp.dot``-into-MXU per step
(``preferred_element_type=f32`` keeps the accumulation in f32 even for bf16
operands), and cast + stored to the output block at the last step.  Ragged
shapes are zero-padded up to the block grid before the call and sliced after
— zeros in K contribute nothing to the dot product.

Block sizes are keyed off ``HardwareSpec.mxu_dim`` (the systolic-array tile):
``default_block`` starts at one MXU tile per dimension and ``tile_space``
spans the small search space the autotune driver measures (multiples of
``mxu_dim``, VMEM-footprint-gated).  Blocks are clamped to the rounded-up
problem size so tiny shapes do not pay for full 128-wide tiles, keeping the
TPU tiling alignments (8 sublanes × 128 lanes).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._util import VMEM_LIMIT_BYTES, tpu_params
from .._util import round_up as _round_up

Block = Tuple[int, int, int]          # (bm, bk, bn)


def _clamp(block: Block, m: int, k: int, n: int) -> Block:
    """Shrink a block to the rounded-up problem size, preserving the TPU
    tiling alignments: 8 on the sublane dims (bm), 128 on the lane dims
    (bk is x's minor dim, bn is w's and the output's)."""
    bm, bk, bn = block
    return (max(8, min(bm, _round_up(m, 8))),
            max(128, min(bk, _round_up(k, 128))),
            max(128, min(bn, _round_up(n, 128))))


def default_block(m: int, k: int, n: int, mxu_dim: int = 128) -> Block:
    """One MXU tile per grid dimension, clamped to the problem."""
    return _clamp((mxu_dim, mxu_dim, mxu_dim), m, k, n)


def tile_space(m: int, k: int, n: int, hw) -> List[Block]:
    """The autotune search space: {1,2,4}·mxu_dim output tiles × {1,2}·mxu_dim
    K depth, deduplicated after clamping and gated on the working set
    (double-buffered x, w and output tiles + the f32 accumulator) fitting
    the VMEM budget the kernel compiles under."""
    d = hw.mxu_dim
    out: List[Block] = []
    seen = set()
    for mm in (1, 2, 4):
        for nn in (1, 2, 4):
            for kk in (1, 2):
                blk = _clamp((mm * d, kk * d, nn * d), m, k, n)
                bm, bk, bn = blk
                working_set = 4 * 2 * (bm * bk + bk * bn + bm * bn) \
                    + 4 * bm * bn
                if working_set > VMEM_LIMIT_BYTES or blk in seen:
                    continue
                seen.add(blk)
                out.append(blk)
    return out or [default_block(m, k, n, d)]


def _kernel(nk: int, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_call(x: jax.Array, w: jax.Array, *,
                block: Optional[Block] = None,
                interpret: bool = False) -> jax.Array:
    """x: (M, K) @ w: (K, N) → (M, N), f32 accumulation on the MXU."""
    m, kd = x.shape
    kd2, n = w.shape
    if kd != kd2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    bm, bk, bn = _clamp(block or default_block(m, kd, n), m, kd, n)
    mp, kp, np_ = _round_up(m, bm), _round_up(kd, bk), _round_up(n, bn)
    if (mp, kp) != (m, kd):
        x = jnp.pad(x, ((0, mp - m), (0, kp - kd)))
    if (kp, np_) != (kd, n):
        w = jnp.pad(w, ((0, kp - kd), (0, np_ - n)))
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kq: (i, kq)),
            pl.BlockSpec((bk, bn), lambda i, j, kq: (kq, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kq: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=tpu_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, w)
    return out[:m, :n] if (mp, np_) != (m, n) else out
