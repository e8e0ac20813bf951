from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax

from ...backends import registry
from ...core.autotune import Tunable
from ...core.ir import Node, OpKind
from .kernel import DEFAULT_BD, rglru_scan_call


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *,
               bd: int = 512, interpret: bool = False):
    """Gated linear recurrence h_t = a_t·h_{t-1} + b_t.
    a, b: (B, T, D); h0: (B, D) → (h: (B,T,D), h_last: (B,D))."""
    return rglru_scan_call(a, b, h0, bd=bd, interpret=interpret)


# -- dispatch-table entries: OpKind.RGLRU_SCAN over (a, b, h0) nodes;
#    the graph-level op yields the full hidden sequence h.

def _clamp_bd(bd: int, d: int) -> int:
    """The kernel's channel block must divide D, and to tile on the TPU be
    a multiple of 128 lanes or D itself: gcd is the largest value that both
    divides D and never exceeds the request, and a divisor off the lane
    grid widens to the whole of D."""
    bd = math.gcd(max(1, int(bd)), d)
    return bd if bd % 128 == 0 else d


def rglru_tune_space(n: Node, hw) -> List[Tuple[int]]:
    """Candidate channel-block lengths for one RGLRU_SCAN node: VPU-lane
    multiples up to the default block plus the whole/half channel dim, each
    clamped to a lane-aligned divisor of D and deduplicated."""
    if len(n.spec.shape) != 3:
        return []
    d = n.spec.shape[-1]
    cands = {_clamp_bd(c, d)
             for c in (hw.lanes, 2 * hw.lanes, 4 * hw.lanes, DEFAULT_BD,
                       d, max(1, d // 2))}
    return [(bd,) for bd in sorted(cands)]


def rglru_refine_space(n: Node, hw, cfg) -> List[Tuple[int]]:
    """SOL-gap planner neighborhood: the channel block must divide D, so
    probe the divisor-clamped half/double of the winning block instead of
    the default raw power-of-two neighbors (which gcd would collapse back
    onto the winner)."""
    d = n.spec.shape[-1]
    bd = int(cfg[0])
    return [(_clamp_bd(c, d),) for c in (bd // 2, bd * 2, bd * 4)]


def _rglru_pallas_impl(n: Node, vals: Sequence[jax.Array],
                       backend: "registry.Backend") -> jax.Array:
    a, b, h0 = vals
    cfg = n.attrs.get("rglru_block")
    bd = _clamp_bd(cfg[0], a.shape[-1]) if cfg else DEFAULT_BD
    return rglru_scan(a, b, h0, bd=bd, interpret=backend.interpret)[0]


def _rglru_ref_impl(n: Node, vals: Sequence[jax.Array],
                    backend: "registry.Backend") -> jax.Array:
    from .ref import rglru_scan_ref
    a, b, h0 = vals
    return rglru_scan_ref(a, b, h0)[0]


registry.register_shared_impl(
    OpKind.RGLRU_SCAN, _rglru_pallas_impl, name="pallas.rglru_scan",
    requires=("pallas",), supports=lambda n: len(n.spec.shape) == 3,
    tunable=Tunable("rglru_block", rglru_tune_space,
                    refine=rglru_refine_space))
registry.register_reference_impl(
    OpKind.RGLRU_SCAN, _rglru_ref_impl, name="ref.rglru_scan")
