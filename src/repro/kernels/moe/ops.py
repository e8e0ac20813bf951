"""Public wrapper and dispatch-table entries of the routed-expert layer.

A MOE node's operands are the rows ``x`` (..., d), the router (d,
n_experts) and the held experts' stacked ``w_gate``, ``w_up`` (n_held, d,
f) and ``w_down`` (n_held, f, d); its attributes ``top_k``, ``first``
(the first held expert), ``norm_topk`` and ``score`` (the router's score
function).  Both impls route inside the node (``ref.route``), so a node
timed on random rows routes as served rows do:

- ``ref.moe``: XLA; the held pairs sorted by expert through
  ``jax.lax.ragged_dot``;
- ``pallas.moe_gmm``: the grouped matmul of ``kernel.py`` over row tiles of
  one expert each; declares its row tile through the ``Tunable`` protocol
  (``node.attrs['moe_block']``).

``OpKind.MOE_COUNT`` (operands ``x`` and the router, the MOE node's
attributes) gives the node's int32 ``(pairs, touched)``; the serving
programs return them beside the logits.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...backends import registry
from ...core.autotune import Tunable
from ...core.ir import Node, OpKind
from .._util import VMEM_LIMIT_BYTES
from . import ref
from .kernel import expert_gmm, pick_blocks, vmem_bytes

ROW_TILES = (8, 64, 128)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "norm_topk", "score", "tm", "interpret"))
def moe(x, router, w_gate, w_up, w_down, *, top_k: int, first: int = 0,
        norm_topk: bool = True, score: str = "softmax", tm: int = 8,
        interpret: bool = False) -> jax.Array:
    """x: (T, d) → the held experts' part of the layer, (T, d), through
    the Pallas grouped matmul with ``tm``-row tiles."""
    n_held, d, f = w_gate.shape
    experts, weights = ref.route(x, router, top_k=top_k, norm_topk=norm_topk,
                                 score=score)
    lay = ref.layout(experts, first, n_held, align=tm)
    xs = ref.gather_rows(x, lay)
    nt = xs.shape[0] // tm
    ends = jnp.cumsum(lay.padded)
    tile_expert = jnp.minimum(
        (ends[None, :] <= tm * jnp.arange(nt)[:, None]).sum(1), n_held - 1)
    n_live = (ends[-1:] // tm).astype(jnp.int32)
    tf, td = pick_blocks(tm, d, f, x.dtype.itemsize)
    y = expert_gmm(xs, tile_expert.astype(jnp.int32), n_live, w_gate, w_up,
                   w_down, tm=tm, tf=tf, td=td, interpret=interpret)
    return ref.combine(y, lay, weights).astype(x.dtype)


def _attrs(n: Node) -> dict:
    a = n.attrs
    return dict(top_k=a["top_k"], first=a.get("first", 0),
                norm_topk=a.get("norm_topk", True),
                score=a.get("score", "softmax"))


def _rows(vals) -> Tuple[jax.Array, tuple]:
    x = vals[0]
    return x.reshape(-1, x.shape[-1]), x.shape


def _moe_ref_impl(n: Node, vals: Sequence[jax.Array],
                  backend: "registry.Backend") -> jax.Array:
    x, shape = _rows(vals)
    return ref.moe_ref(x, *vals[1:5], **_attrs(n)).reshape(shape)


def _row_tile(n: Node) -> int:
    cfg = n.attrs.get("moe_block")
    return int(cfg[0]) if cfg else moe_tune_space(n, None)[-1][0]


def _moe_pallas_impl(n: Node, vals: Sequence[jax.Array],
                     backend: "registry.Backend") -> jax.Array:
    x, shape = _rows(vals)
    return moe(x, *vals[1:5], tm=_row_tile(n), interpret=backend.interpret,
               **_attrs(n)).reshape(shape)


def _expected_rows(n: Node) -> float:
    """Pairs each expert receives on average under uniform routing."""
    rows = 1
    for s in n.spec.shape[:-1]:
        rows *= s
    n_experts = n.inputs[1].spec.shape[-1]
    return rows * n.attrs["top_k"] / n_experts


def moe_tune_space(n: Node, hw) -> List[Tuple[int]]:
    """Row tiles for one MOE node: the two largest of ``ROW_TILES`` that an
    expert's expected pairs fill (8 where they fill none), whose working set
    fits the VMEM budget."""
    fill = [t for t in ROW_TILES if t <= max(8.0, _expected_rows(n))]
    _, d, f = n.inputs[2].spec.shape
    item = jnp.dtype(n.spec.dtype).itemsize
    fits = [t for t in fill[-2:]
            if vmem_bytes(t, *pick_blocks(t, d, f, item), item)
            <= VMEM_LIMIT_BYTES]
    return [(t,) for t in fits or [8]]


def _supports(n: Node) -> bool:
    return (len(n.inputs) == 5 and len(n.inputs[2].spec.shape) == 3
            and n.spec.dtype in ("float32", "bfloat16"))


def _count_impl(n: Node, vals: Sequence[jax.Array],
                backend: "registry.Backend") -> jax.Array:
    x, _ = _rows(vals)
    a = _attrs(n)
    experts, _ = ref.route(x, vals[1], top_k=a["top_k"],
                           norm_topk=a["norm_topk"], score=a["score"])
    return ref.held_counts(experts, a["first"], n.attrs["n_held"])


registry.register_shared_impl(
    OpKind.MOE, _moe_pallas_impl, name="pallas.moe_gmm",
    requires=("pallas",), supports=_supports,
    tunable=Tunable("moe_block", moe_tune_space))
registry.register_reference_impl(OpKind.MOE, _moe_ref_impl, name="ref.moe")
registry.register_reference_impl(OpKind.MOE_COUNT, _count_impl,
                                 name="ref.moe_count")
