"""Public wrapper for the DFP fused kernel + its dispatch-table entry.

Registered as the shared-tier impl of ``OpKind.FUSED``: any backend with the
'pallas' capability lowers DFP fusion groups to one VMEM-resident Pallas
program; everyone else falls back to the reference tier, which composes
op-at-a-time (XLA then fuses the chain — the 'vendor stack' flavour).

The impl declares a ``Tunable`` over fusion-group sizing: a config is
``(block_rows, max_group)`` pinned as ``node.attrs['dfp_block']`` —
``block_rows`` overrides the VMEM-budget row-block heuristic, and
``max_group`` caps how many instructions run as one kernel launch
(``program.split_program`` cuts the chain at its legal split points, the
carried value paying one HBM round-trip per cut)."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax

from ...backends import registry
from ...core.autotune import Tunable
from ...core.ir import Node, OpKind
from .._util import VMEM_LIMIT_BYTES
from .kernel import choose_block_rows, clamp_block_rows, dfp_fused_call
from .program import Program, split_program

# ops the Pallas dfp_fused kernel supports as a single VMEM-resident program
DFP_KERNEL_OPS = {
    OpKind.RELU, OpKind.GELU, OpKind.SILU, OpKind.SIGMOID, OpKind.TANH,
    OpKind.EXP, OpKind.ADD, OpKind.SUB, OpKind.MUL, OpKind.DIV,
    OpKind.BIAS_ADD, OpKind.SCALE, OpKind.SOFTCAP, OpKind.RMSNORM,
    OpKind.LAYERNORM, OpKind.IDENTITY, OpKind.DROPOUT,
}


def dfp_fused(prog: Program, operands: Sequence[jax.Array],
              interpret: bool = False, block_rows: int = 0) -> jax.Array:
    # chain output shape == shape of the first 'full' operand
    full = [o for o, k in zip(operands, prog.operand_kinds) if k == "full"]
    if not full:
        raise ValueError("dfp_fused needs at least one full-shape operand")
    out_shape = tuple(full[0].shape)
    out_dtype = full[0].dtype
    return dfp_fused_call(prog, list(operands), out_shape, out_dtype,
                          block_rows=block_rows, interpret=interpret)


def dfp_fused_segmented(prog: Program, operands: Sequence[jax.Array],
                        max_group: int, *, block_rows: int = 0,
                        interpret: bool = False) -> jax.Array:
    """Run a program as ≤``max_group``-instruction kernel launches, the cut
    values round-tripping through HBM between launches."""
    out = None
    for seg, sel in split_program(prog, max_group):
        vals = [out if s == "carry" else operands[s] for s in sel]
        out = dfp_fused(seg, vals, interpret=interpret,
                        block_rows=block_rows)
    return out


def _supports_chain(n: Node) -> bool:
    body = n.body
    return (bool(body)
            and all(b.op in DFP_KERNEL_OPS for b in body)
            and all(b.spec.shape == body[-1].spec.shape
                    or b.op is OpKind.BIAS_ADD for b in body))


def dfp_tune_space(n: Node, hw) -> List[Tuple[int, int]]:
    """Candidate (block_rows, max_group) configs for one FUSED node: the
    VMEM-budget heuristic row block plus coarser/finer power-of-two blocks
    (clamped and gated for the body's register count on half the kernels'
    VMEM budget, the other half left to the pipeline's double-buffered
    input and output blocks), crossed with the
    whole chain vs a half-length fusion split when the body is long enough
    to have split points worth measuring."""
    shape = n.spec.shape
    body = n.body
    if len(shape) < 2 or not body:
        return []
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    n_regs = len(body) + 3
    auto = choose_block_rows(rows, d, n_regs, 4)
    brs = sorted({clamp_block_rows(c, rows)
                  for c in (auto, 128, 512, 2048)
                  if n_regs * clamp_block_rows(c, rows) * max(d, 128) * 4
                  <= VMEM_LIMIT_BYTES // 2})
    groups = [len(body)]
    if len(body) >= 4:
        groups.append((len(body) + 1) // 2)
    return [(br, grp) for br in brs for grp in groups]


def _node_config(n: Node) -> Tuple[int, int]:
    cfg = n.attrs.get("dfp_block")
    if not cfg:
        return 0, 0
    return int(cfg[0]), int(cfg[1]) if len(cfg) > 1 else 0


def _dfp_fused_impl(n: Node, vals: Sequence[jax.Array],
                    backend: "registry.Backend") -> jax.Array:
    from ...core.executor import compose_fused
    from .program import encode_program
    env = {id(i): v for i, v in zip(n.inputs, vals)}
    try:
        program, operands = encode_program(n, env)
    except NotImplementedError:
        program = None
    if program is None:   # shapes the kernel doesn't cover — compose instead
        return compose_fused(n, vals, backend)
    block_rows, max_group = _node_config(n)
    if max_group and max_group < len(program.instrs):
        return dfp_fused_segmented(program, operands, max_group,
                                   block_rows=block_rows,
                                   interpret=backend.interpret)
    return dfp_fused(program, operands, interpret=backend.interpret,
                     block_rows=block_rows)


registry.register_shared_impl(
    OpKind.FUSED, _dfp_fused_impl, name="pallas.dfp_fused",
    requires=("pallas",), supports=_supports_chain, memory="streamed",
    tunable=Tunable("dfp_block", dfp_tune_space))
