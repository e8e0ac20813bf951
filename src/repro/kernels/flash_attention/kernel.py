"""Flash attention (TPU Pallas): causal GQA with optional local window and
logit softcap — the DNN-module flavour of the chunked online-softmax scan in
``models.layers``.

Grid: (batch, q_head, q_block).  The kv-head index is derived from the
q-head index (GQA: h // group).  K/V for one kv head live in VMEM whole,
and the pipeline double-buffers them: ``4·S·hd·itemsize`` bytes, 32 MiB at
S=16k, hd=128 in f32 and 64 MiB at 32k.  :func:`vmem_bytes` is the budget
the ``supports`` predicate checks against the limit the kernel compiles
under, so longer sequences elect the reference.  The kernel streams kv
blocks out of them with an online-softmax carry in VREGs.  Causality bounds
the kv loop dynamically — upper = ceil((q_hi+1)/bk) — so the wasted-block
count is zero.

BlockSpecs:
  q:   (1, 1, bq, hd)   index (b, h, i) -> (b, h, i, 0)
  k/v: (1, 1, S,  hd)   index (b, h, i) -> (b, h // group, 0, 0)
  o:   (1, 1, bq, hd)
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._util import round_up as _round_up
from .._util import tpu_params

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG = -1e30


def blocks(s: int, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK
           ) -> Tuple[int, int, int]:
    """The (bq, bk, padded S) a call with these requested blocks runs."""
    bq = min(bq, _round_up(s, 8))     # keep the 8-sublane alignment for
    bk = min(bk, _round_up(s, 8))     # short sequences instead of bq = s
    if max(bq, bk) % min(bq, bk):     # incommensurate pair: collapse to the
        bq = bk = min(bq, bk)         # smaller instead of an lcm-sized pad
    return bq, bk, _round_up(s, max(bq, bk))   # padded S divides both


def vmem_bytes(s: int, hd: int, bq: int, bk: int, itemsize: int) -> int:
    """VMEM one grid step needs: double-buffered whole-sequence K/V and
    q/o blocks, plus the f32 logits tile, accumulator and kv blocks."""
    bq, bk, sp = blocks(s, bq, bk)
    return (4 * sp * hd * itemsize + 4 * bq * hd * itemsize
            + 4 * (2 * bq * bk + bq * hd + 2 * bk * hd))


def _kernel(bq: int, bk: int, causal: bool, window: int, cap: float,
            scale: float, s_len: int, q_ref, k_ref, v_ref, o_ref):
    i = pl.program_id(2)
    s = k_ref.shape[2]
    nk = s // bk
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale        # (bq, hd)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, 0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, bk)
        if cap:
            logits = jnp.tanh(logits / cap) * cap
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= (q_pos - k_pos) < window
        if s_len != s:      # ragged tail: padded key positions contribute 0
            mask &= k_pos < s_len
        logits = jnp.where(mask, logits, NEG)
        m_new = jnp.maximum(m, logits.max(axis=1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    hd = q_ref.shape[3]
    init = (jnp.full((bq,), -jnp.inf, jnp.float32),
            jnp.zeros((bq,), jnp.float32),
            jnp.zeros((bq, hd), jnp.float32))
    if causal:
        hi = jnp.minimum(nk, pl.cdiv((i + 1) * bq, bk))
        lo = jnp.maximum(0, (i * bq - window) // bk) if window else 0
    else:
        hi, lo = nk, 0
    m, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    o = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, 0, :, :] = o.astype(o_ref.dtype)


def flash_attention_call(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         cap: float = 0.0, bq: int = DEFAULT_BQ,
                         bk: int = DEFAULT_BK,
                         interpret: bool = False) -> jax.Array:
    """q: (B, H, S, hd); k, v: (B, KV, S, hd).  Returns (B, H, S, hd).

    Ragged sequence lengths are zero-padded up to the block grid and sliced
    back after the call (like ``kernels/matmul``): padded *key* positions
    are masked inside the kernel (a zero-padded key would score logit 0,
    not -inf), while padded *query* rows compute garbage that the final
    slice drops.
    """
    b, h, s, hd = q.shape
    kv = k.shape[1]
    group = h // kv
    bq, bk, sp = blocks(s, bq, bk)
    if sp != s:
        pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    scale = 1.0 / math.sqrt(hd)
    grid = (b, h, sp // bq)
    kernel = functools.partial(_kernel, bq, bk, causal, window, cap, scale, s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, sp, hd),
                         lambda b_, h_, i, g=group: (b_, h_ // g, 0, 0)),
            pl.BlockSpec((1, 1, sp, hd),
                         lambda b_, h_, i, g=group: (b_, h_ // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b_, h_, i: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sp, hd), q.dtype),
        compiler_params=tpu_params("parallel", "parallel", "parallel"),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :s, :] if sp != s else out
