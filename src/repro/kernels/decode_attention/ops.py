"""Public wrapper: accepts model-layout decode-attention operands.

The node carries six operands in model layout —

    q       (B, 1, H, hd)    this step's query projection
    k, v    (B, S, KV, hd)   the KV cache gathered from the SlotArena
    k_new   (B, 1, KV, hd)   the step's key projection (cache position len)
    v_new   (B, 1, KV, hd)   the step's value projection
    lens    (B,) int32       valid cache rows per sequence

— and produces (B, 1, H, hd).  The Pallas impl declares a ``Tunable`` over
the kv block length: the autotune sweep measures every candidate and the
election pass pins the winner on the node as ``node.attrs['decode_block']``,
which the impl reads back at lowering time (one pin per decode cache
bucket, since the cache keys DECODE_ATTENTION on the KV-cache shape)."""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...backends import registry
from ...core.autotune import Tunable
from ...core.ir import Node, OpKind
from .._util import VMEM_LIMIT_BYTES, round_up
from .kernel import DEFAULT_BK, decode_attention_call, vmem_bytes


@functools.partial(jax.jit, static_argnames=("window", "cap", "bk",
                                             "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, lens: jax.Array, *,
                     window: int = 0, cap: float = 0.0, bk: int = DEFAULT_BK,
                     interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, hd); k, v: (B, S, KV, hd); k_new, v_new: (B, 1, KV, hd);
    lens: (B,) int32 → (B, 1, H, hd)."""
    o = decode_attention_call(
        q[:, 0], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        k_new[:, 0], v_new[:, 0], lens,
        window=window, cap=cap, bk=bk, interpret=interpret)
    return o[:, None]


# -- dispatch-table entries: OpKind.DECODE_ATTENTION --------------------------

def _attrs(n: Node) -> dict:
    return dict(window=n.attrs.get("window", 0),
                cap=n.attrs.get("cap", 0.0))


def _fits(n: Node, bk: int) -> bool:
    """Whether one grid step at kv block ``bk`` fits the VMEM budget the
    kernel compiles under (the whole per-(batch, kv head) cache is
    resident)."""
    _, s, kv, hd = n.inputs[1].spec.shape      # k_cache is (B, S, KV, hd)
    bk = min(bk, round_up(s, 8))
    group = n.spec.shape[2] // kv
    return vmem_bytes(round_up(s, bk), hd, group, bk,
                      jnp.dtype(n.spec.dtype).itemsize) <= VMEM_LIMIT_BYTES


def _supports(n: Node) -> bool:
    return (len(n.spec.shape) == 4 and len(n.inputs) == 6
            and len(n.inputs[1].spec.shape) == 4 and _fits(n, DEFAULT_BK))


def decode_tune_space(n: Node, hw) -> List[Tuple[int]]:
    """Candidate kv block lengths for one DECODE_ATTENTION node: powers of
    two up to the default block, clamped to the (8-sublane rounded) cache
    bucket length, deduplicated, and gated on the whole per-head cache plus
    the block-sized working set fitting the kernels' VMEM budget."""
    if len(n.inputs) < 2 or len(n.inputs[1].spec.shape) != 4:
        return []
    s = n.inputs[1].spec.shape[1]
    cap = round_up(s, 8)
    cands: List[Tuple[int]] = []
    seen = set()
    size = 32
    while size <= DEFAULT_BK:
        bk = min(size, cap)
        if bk not in seen and _fits(n, bk):
            seen.add(bk)
            cands.append((bk,))
        size *= 2
    return cands


def _decode_attention_pallas_impl(n: Node, vals: Sequence[jax.Array],
                                  backend: "registry.Backend") -> jax.Array:
    q, k, v, k_new, v_new, lens = vals
    cfg = n.attrs.get("decode_block")
    bk = int(cfg[0]) if cfg else DEFAULT_BK
    return decode_attention(q, k, v, k_new, v_new, lens, bk=bk,
                            interpret=backend.interpret, **_attrs(n))


def _decode_attention_ref_impl(n: Node, vals: Sequence[jax.Array],
                               backend: "registry.Backend") -> jax.Array:
    from .ref import decode_attention_ref
    q, k, v, k_new, v_new, lens = vals
    o = decode_attention_ref(q[:, 0], k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), k_new[:, 0],
                             v_new[:, 0], lens, **_attrs(n))
    return o[:, None]


registry.register_shared_impl(
    OpKind.DECODE_ATTENTION, _decode_attention_pallas_impl,
    name="pallas.decode_attention", requires=("pallas",),
    supports=_supports,
    tunable=Tunable("decode_block", decode_tune_space))
registry.register_reference_impl(
    OpKind.DECODE_ATTENTION, _decode_attention_ref_impl,
    name="ref.decode_attention",
    memory="roundtrip")   # materializes the (B, H, S) score rows
