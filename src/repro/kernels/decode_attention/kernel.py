"""Single-token decode attention (TPU Pallas): one query row per sequence
against that sequence's KV cache — the O(1)-per-token half of the served
prefill/decode split (``distributed/steps.py:make_serve_steps`` is the SPMD
ancestor of the same shape).

Grid: (batch, kv_head).  One step holds the ``group = H // KV`` query heads
that share a kv head (GQA: q head ``h`` reads kv head ``h // group``), so a
block's last two dims are ``(group, hd)`` or ``(S, hd)`` in full — the
layout Mosaic's (8, 128) tiling accepts for any B and H.  The whole cache
of one (batch, kv head) lives in VMEM (double-buffered K and V:
``4·S·hd·itemsize``, 1 MiB at S=512, hd=128, f32; :func:`vmem_bytes` is the
budget the ``supports`` predicate checks) and the kernel streams it in
``bk``-row blocks with an online-softmax carry, exactly like the prefill
flash kernel but with one query row per head.  The per-row cache lengths
arrive as a scalar-prefetch operand in SMEM: the kv loop's upper bound is
``ceil(len/bk)``, so a short resident sequence reads only its own rows —
per-step work is proportional to the *actual* cache length, never to the
bucket.  The step's freshly projected (k_new, v_new) pair — position
``len``, computed in the same forward — is folded into the softmax after
the loop, resolving the same-layer chicken-and-egg without a cache write
inside the kernel.

BlockSpecs (index maps also receive the prefetched ``lens``):
  q, o:        (1, 1, group, hd)   index (b, g) -> (b, g, 0, 0)
  k/v:         (1, 1, S, hd)       index (b, g) -> (b, g, 0, 0)
  k_new/v_new: (1, 1, 1, hd)       index (b, g) -> (b, g, 0, 0)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._util import round_up as _round_up
from .._util import tpu_params

DEFAULT_BK = 512
NEG = -1e30


def vmem_bytes(sp: int, hd: int, group: int, bk: int, itemsize: int) -> int:
    """VMEM one grid step needs: double-buffered K/V caches, q/o/k_new/v_new
    blocks (sublane-padded), plus the f32 kv block and logits rows."""
    gp = _round_up(group, 8)
    return (4 * sp * hd * itemsize
            + 4 * (gp + 8) * hd * itemsize
            + 4 * (2 * bk * hd + 2 * gp * bk + gp * hd))


def _kernel(bk: int, window: int, cap: float, scale: float,
            lens_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref):
    s = k_ref.shape[2]
    q = q_ref[0, 0].astype(jnp.float32) * scale              # (group, hd)
    length = lens_ref[pl.program_id(0)]                      # valid rows

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (group, bk)
        if cap:
            logits = jnp.tanh(logits / cap) * cap
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = k_pos < length          # ragged tail + bucket padding rows
        if window:                     # query position is `length`
            mask &= (length - k_pos) < window
        logits = jnp.where(mask, logits, NEG)
        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    group, hd = q.shape
    init = (jnp.full((group, 1), -jnp.inf, jnp.float32),
            jnp.zeros((group, 1), jnp.float32),
            jnp.zeros((group, hd), jnp.float32))
    hi = jnp.minimum(s // bk, pl.cdiv(length, bk))
    lo = jnp.maximum(0, length - window) // bk if window else 0
    m, l, acc = jax.lax.fori_loop(lo, hi, body, init)

    # fold in the new (k, v) pair at position `length` (distance 0: always
    # causal-visible and inside any window)
    kn = kn_ref[0, 0].astype(jnp.float32)                    # (1, hd)
    vn = vn_ref[0, 0].astype(jnp.float32)
    logit_n = (q * kn).sum(axis=1, keepdims=True)            # (group, 1)
    if cap:
        logit_n = jnp.tanh(logit_n / cap) * cap
    m_fin = jnp.maximum(m, logit_n)
    corr = jnp.exp(m - m_fin)
    p_n = jnp.exp(logit_n - m_fin)
    l_fin = l * corr + p_n
    acc_fin = acc * corr + p_n * vn
    o = acc_fin / jnp.maximum(l_fin, 1e-30)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def decode_attention_call(q: jax.Array, k: jax.Array, v: jax.Array,
                          k_new: jax.Array, v_new: jax.Array,
                          lens: jax.Array, *, window: int = 0,
                          cap: float = 0.0, bk: int = DEFAULT_BK,
                          interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k, v: (B, KV, S, hd); k_new, v_new: (B, KV, hd);
    lens: (B,) int32.  Returns (B, H, hd).

    The cache is zero-padded along S up to the block grid; padded rows are
    masked inside the kernel (``k_pos < lens[b]``), so any garbage beyond a
    row's valid length — bucket padding included — contributes nothing.
    """
    b, h, hd = q.shape
    kv, s = k.shape[1], k.shape[2]
    group = h // kv
    bk = min(bk, _round_up(s, 8))
    sp = _round_up(s, bk)
    if sp != s:
        pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_kernel, bk, window, cap, scale)

    def spec(rows):
        return pl.BlockSpec((1, 1, rows, hd),
                            lambda b_, g_, lens_: (b_, g_, 0, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv),
            in_specs=[spec(group), spec(sp), spec(sp), spec(1), spec(1)],
            out_specs=spec(group)),
        out_shape=jax.ShapeDtypeStruct((b, kv, group, hd), q.dtype),
        compiler_params=tpu_params("parallel", "parallel"),
        interpret=interpret,
    )(lens.astype(jnp.int32).reshape(b), q.reshape(b, kv, group, hd), k, v,
      k_new.reshape(b, kv, 1, hd), v_new.reshape(b, kv, 1, hd))
    return out.reshape(b, h, hd)
