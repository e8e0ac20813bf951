"""RWKV6 WKV Pallas kernel (TPU).

Per (batch, head): walks T steps with the (hd_k × hd_v) state matrix
resident in VMEM (64×64 f32 = 16 KiB), computing

  o_t = r_t · (S_{t-1} + (u ⊙ k_t) vᵀ_t)
  S_t = diag(w_t) S_{t-1} + k_t vᵀ_t

The matrix state never round-trips to HBM during the scan — the DFP
insight applied to linear attention.  Grid: (B, H, T/bt) with the time
dimension innermost: TPU grids iterate the last dimension sequentially, so
the state carries across time blocks in a VMEM scratch (the same pattern
as the matmul kernel's K-loop accumulator).  ``bt`` bounds how much of the
(T, hd) head slice one launch holds in VMEM — the tunable knob the
autotune sweep measures (clamped to a divisor of T via gcd).

The wrapper moves heads ahead of time — (B, H, T, hd) — so every block's
last two dims are (bt, hd), which Mosaic's (8, 128) tiling accepts when
``bt`` is a multiple of 8 or the whole of T; ``u`` travels as (H, 1, hd).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._util import tpu_params


def _kernel(bt: int, nt: int, r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            o_ref, sl_ref, s_ref):
    tq = pl.program_id(2)

    @pl.when(tq == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                    # (1, hd)

    def body(t, s):
        row = pl.ds(t, 1)
        r = r_ref[0, 0, row, :].astype(jnp.float32)     # (1, hd)
        k = k_ref[0, 0, row, :].astype(jnp.float32)
        v = v_ref[0, 0, row, :].astype(jnp.float32)
        w = w_ref[0, 0, row, :].astype(jnp.float32)     # log decay ≤ 0
        kv = k.T * v                                    # (hd_k, hd_v)
        o = ((s + (u * k).T * v) * r.T).sum(axis=0, keepdims=True)
        o_ref[0, 0, row, :] = o.astype(o_ref.dtype)
        return jnp.exp(w).T * s + kv

    s_ref[...] = jax.lax.fori_loop(0, bt, body, s_ref[...])

    @pl.when(tq == nt - 1)
    def _store():
        sl_ref[0, 0] = s_ref[...].astype(sl_ref.dtype)


def rwkv6_scan_call(r, k, v, logw, u, s0, *, bt: int = 0,
                    interpret: bool = False):
    """r,k,v,logw: (B, T, H, hd); u: (H, hd); s0: (B, H, hd, hd).
    Returns (o: (B,T,H,hd), s_last: (B,H,hd,hd))."""
    b, t, h, hd = r.shape
    bt = math.gcd(max(1, bt), t) if bt else t
    nt = t // bt
    grid = (b, h, nt)
    kernel = functools.partial(_kernel, bt, nt)
    seq = pl.BlockSpec((1, 1, bt, hd), lambda i, j, tq: (i, j, tq, 0))
    state = pl.BlockSpec((1, 1, hd, hd), lambda i, j, tq: (i, j, 0, 0))
    heads_first = [x.transpose(0, 2, 1, 3) for x in (r, k, v, logw)]
    o, s_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((1, 1, hd), lambda i, j, tq: (j, 0, 0)),
                  state],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=tpu_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*heads_first, u.reshape(h, 1, hd), s0)
    return o.transpose(0, 2, 1, 3), s_last
