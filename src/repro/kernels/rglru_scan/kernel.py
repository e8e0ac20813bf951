"""RG-LRU linear-recurrence Pallas kernel (TPU).

h_t = a_t ⊙ h_{t-1} + b_t over time, per channel — the gated linear
recurrence at the heart of RecurrentGemma/Griffin.  The recurrence is
elementwise over channels, so the grid tiles (batch × channel-blocks) and
each program walks T sequentially with the state vector resident in VREGs —
the DFP principle (state never leaves the core) applied to an RNN.

BlockSpecs: a, b: (1, T, bd); h0 and h_last travel as (B, 1, D) so their
blocks (1, 1, bd) keep a whole unit dim in the sublane slot; outputs
likewise.  ``bd`` is a multiple of 128 lanes or the whole of D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._util import tpu_params

DEFAULT_BD = 512


def _kernel(t_total: int, a_ref, b_ref, h0_ref, o_ref, hl_ref):
    h0 = h0_ref[0].astype(jnp.float32)                      # (1, bd)

    def body(t, h):
        a = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a * h + b
        o_ref[0, pl.ds(t, 1), :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, t_total, body, h0)
    hl_ref[0] = h.astype(hl_ref.dtype)


def rglru_scan_call(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                    bd: int = DEFAULT_BD, interpret: bool = False):
    """a, b: (B, T, D) decay/input; h0: (B, D).  Returns (h, h_last)."""
    bsz, t, d = a.shape
    bd = min(bd, d)
    if d % bd:
        raise ValueError(f"d={d} must divide bd={bd}")
    grid = (bsz, d // bd)
    kernel = functools.partial(_kernel, t)
    seq = pl.BlockSpec((1, t, bd), lambda i, j: (i, 0, j))
    state = pl.BlockSpec((1, 1, bd), lambda i, j: (i, 0, j))
    h, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq, seq, state],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, d), a.dtype),
            jax.ShapeDtypeStruct((bsz, 1, d), a.dtype),
        ],
        compiler_params=tpu_params("parallel", "parallel"),
        interpret=interpret,
    )(a, b, h0.reshape(bsz, 1, d))
    return h, h_last.reshape(bsz, d)
