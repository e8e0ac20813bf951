"""Public wrapper: accepts model-layout (B, S, H, hd) tensors.

The Pallas impl declares a ``Tunable`` over the (bq, bk) block sizes: the
autotune sweep measures every candidate pair and the election pass pins the
winner on the node as ``node.attrs['attn_block']``, which the impl reads
back at lowering time."""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...backends import registry
from ...core.autotune import Tunable
from ...core.ir import Node, OpKind
from .._util import VMEM_LIMIT_BYTES, round_up
from .kernel import DEFAULT_BK, DEFAULT_BQ, flash_attention_call, vmem_bytes


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, cap: float = 0.0,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) → (B, S, H, hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_call(qt, kt, vt, causal=causal, window=window,
                             cap=cap, bq=bq, bk=bk, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


# -- dispatch-table entries: OpKind.ATTENTION over (q, k, v) nodes -----------

def _attrs(n: Node) -> dict:
    return dict(causal=n.attrs.get("causal", True),
                window=n.attrs.get("window", 0),
                cap=n.attrs.get("cap", 0.0))


def _fits(n: Node, bq: int, bk: int) -> bool:
    """Whether one grid step at blocks (bq, bk) — whole-sequence K/V
    resident — fits the VMEM budget the kernel compiles under."""
    _, s, _, hd = n.spec.shape
    return vmem_bytes(s, hd, bq, bk,
                      jnp.dtype(n.spec.dtype).itemsize) <= VMEM_LIMIT_BYTES


def _supports(n: Node) -> bool:
    return len(n.spec.shape) == 4 and _fits(n, DEFAULT_BQ, DEFAULT_BK)


def attn_tune_space(n: Node, hw) -> List[Tuple[int, int]]:
    """Candidate (bq, bk) block pairs for one ATTENTION node: powers of two
    from one VPU row block up to the default block, clamped to the (8-sublane
    rounded) sequence length, deduplicated, and gated on the resident K/V,
    the f32 logits tile and the q/accumulator blocks fitting the kernels'
    VMEM budget."""
    b, s, h, hd = n.spec.shape
    cap = min(DEFAULT_BQ, round_up(s, 8))
    cands: List[Tuple[int, int]] = []
    seen = set()
    size = 32
    sizes = []
    while size <= max(DEFAULT_BQ, DEFAULT_BK):
        sizes.append(size)
        size *= 2
    for bq in sizes:
        for bk in sizes:
            cfg = (min(bq, cap), min(bk, cap))
            if cfg in seen or not _fits(n, *cfg):
                continue
            seen.add(cfg)
            cands.append(cfg)
    return cands


def _attention_pallas_impl(n: Node, vals: Sequence[jax.Array],
                           backend: "registry.Backend") -> jax.Array:
    q, k, v = vals
    cfg = n.attrs.get("attn_block")
    bq, bk = (int(cfg[0]), int(cfg[1])) if cfg else (DEFAULT_BQ, DEFAULT_BK)
    return flash_attention(q, k, v, bq=bq, bk=bk,
                           interpret=backend.interpret, **_attrs(n))


def _attention_ref_impl(n: Node, vals: Sequence[jax.Array],
                        backend: "registry.Backend") -> jax.Array:
    from .ref import flash_attention_ref
    q, k, v = vals
    o = flash_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), **_attrs(n))
    return o.transpose(0, 2, 1, 3)


registry.register_shared_impl(
    OpKind.ATTENTION, _attention_pallas_impl, name="pallas.flash_attention",
    requires=("pallas",), supports=_supports,
    tunable=Tunable("attn_block", attn_tune_space))
registry.register_reference_impl(
    OpKind.ATTENTION, _attention_ref_impl, name="ref.attention",
    memory="roundtrip")   # materializes the S×S score matrix
