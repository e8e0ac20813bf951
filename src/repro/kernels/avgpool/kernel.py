"""AveragePooling — the paper's own Listing 3, as a TPU Pallas kernel.

The paper shows the same DFP loop nest emitted for ISPC (CPU), CUDA and
NCC (SX-Aurora); this is the fourth flavour.  The (OP1, OP0) spatial loops
of the listing become the VPU lane grid; the channel loop (OC0x, the
paper's ``taskIndex``) becomes the Pallas grid dimension; the K1/K2 kernel
loops unroll in VREGs — one HBM read per input tile, depth-first.

Layout NCHW, stride 1, VALID padding (matching the listing's 3×3/9 form).
``bc`` blocks the channel grid: each program holds (bc, H, W) in VMEM and
pools bc channels per launch — the tunable knob the autotune sweep
measures (``bc`` is clamped to a divisor of C via gcd).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._util import tpu_params


def _kernel(kh: int, kw: int, out_h: int, out_w: int, x_ref, o_ref):
    bc = x_ref.shape[1]
    acc = jnp.zeros((bc, out_h, out_w), jnp.float32)
    for k1 in range(kh):                 # the listing's K1/K2 unrolled
        for k2 in range(kw):
            acc = acc + x_ref[0, :, k1:k1 + out_h, k2:k2 + out_w].astype(
                jnp.float32)
    o_ref[0, :, :, :] = (acc / float(kh * kw)).astype(o_ref.dtype)


def avgpool_call(x: jax.Array, kh: int = 3, kw: int = 3, *,
                 bc: int = 1, interpret: bool = False) -> jax.Array:
    """x: (N, C, H, W) → (N, C, H-kh+1, W-kw+1); stride 1, VALID."""
    n, c, h, w = x.shape
    bc = math.gcd(max(1, bc), c)
    out_h, out_w = h - kh + 1, w - kw + 1
    kernel = functools.partial(_kernel, kh, kw, out_h, out_w)
    return pl.pallas_call(
        kernel,
        grid=(n, c // bc),               # OC0x of the listing, bc-blocked
        in_specs=[pl.BlockSpec((1, bc, h, w), lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((1, bc, out_h, out_w),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c, out_h, out_w), x.dtype),
        compiler_params=tpu_params("parallel", "parallel"),
        interpret=interpret,
    )(x)
