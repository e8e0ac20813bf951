"""Public wrapper + dispatch-table entries for the RWKV6 WKV recurrence.

The Pallas impl declares a ``Tunable`` over the time-block length: a config
``(bt,)`` pinned as ``node.attrs['rwkv6_block']`` bounds how many timesteps
one kernel launch holds in VMEM, the state matrix carrying across blocks in
scratch."""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax

from ...backends import registry
from ...core.autotune import Tunable
from ...core.ir import Node, OpKind
from .kernel import rwkv6_scan_call


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def rwkv6_scan(r, k, v, logw, u, s0, *, bt: int = 0,
               interpret: bool = False):
    """RWKV6 WKV recurrence.  r,k,v,logw: (B,T,H,hd); u: (H,hd);
    s0: (B,H,hd,hd) → (o: (B,T,H,hd), s_last)."""
    return rwkv6_scan_call(r, k, v, logw, u, s0, bt=bt, interpret=interpret)


# -- dispatch-table entries: OpKind.RWKV6_SCAN over (r, k, v, logw, u, s0);
#    the graph-level op yields the per-token output o.

def rwkv6_tune_space(n: Node, hw) -> List[Tuple[int]]:
    """Candidate time-block lengths: sublane multiples up to the whole
    sequence, clamped to divisors of T (gcd) and deduplicated; a block off
    the 8-sublane grid is kept only when it is the whole of T."""
    if len(n.spec.shape) != 4:
        return []
    t = n.spec.shape[1]
    cands = {math.gcd(v, t) for v in (hw.sublanes, 4 * hw.sublanes,
                                      16 * hw.sublanes, t, max(1, t // 2))}
    return [(bt,) for bt in sorted(cands) if bt % 8 == 0 or bt == t]


def rwkv6_refine_space(n: Node, hw, cfg) -> List[Tuple[int]]:
    """SOL-gap planner neighborhood: the time block must divide T, so probe
    divisor-clamped half/double steps around the winner."""
    t = n.spec.shape[1]
    bt = int(cfg[0])
    cands = (math.gcd(max(1, c), t) for c in (bt // 2, bt * 2, bt * 4))
    return [(c,) for c in cands if c % 8 == 0 or c == t]


def _rwkv6_pallas_impl(n: Node, vals: Sequence[jax.Array],
                       backend: "registry.Backend") -> jax.Array:
    cfg = n.attrs.get("rwkv6_block")
    bt = int(cfg[0]) if cfg else 0
    return rwkv6_scan(*vals, bt=bt, interpret=backend.interpret)[0]


def _rwkv6_ref_impl(n: Node, vals: Sequence[jax.Array],
                    backend: "registry.Backend") -> jax.Array:
    from .ref import rwkv6_scan_ref
    return rwkv6_scan_ref(*vals)[0]


registry.register_shared_impl(
    OpKind.RWKV6_SCAN, _rwkv6_pallas_impl, name="pallas.rwkv6_scan",
    requires=("pallas",), supports=lambda n: len(n.spec.shape) == 4,
    tunable=Tunable("rwkv6_block", rwkv6_tune_space,
                    refine=rwkv6_refine_space))
registry.register_reference_impl(
    OpKind.RWKV6_SCAN, _rwkv6_ref_impl, name="ref.rwkv6_scan")
