"""DFP fused-chain Pallas kernel (TPU).

One HBM→VMEM round-trip for an entire memory-bound op chain — the TPU-native
version of the paper's depth-first parallelism.  Input is viewed as
(rows, d); the grid tiles rows; each block holds (block_rows, d) in VMEM and
the whole instruction program executes on the resident block.  Norm ops
reduce over d, so d is kept un-tiled inside the block (and block_rows is
shrunk to respect the VMEM budget instead).

BlockSpecs:
  main input / 'full' operands / output: (block_rows, d) tiles over the grid
  'vec' operands:                        (1, d), same block for every step
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._util import tpu_params
from .program import Program

# VMEM working-set budget per block (bytes); conservative vs 128 MiB/core so
# several live registers + double buffering fit.
_VMEM_BUDGET = 8 * 1024 * 1024
_SUBLANE = 8
_LANE = 128


def clamp_block_rows(br: int, rows: int) -> int:
    """Snap a row-block request to the 8-sublane tile and the (rounded-up)
    problem size."""
    br = max(_SUBLANE, (br // _SUBLANE) * _SUBLANE)
    return min(br, max(_SUBLANE,
                       ((rows + _SUBLANE - 1) // _SUBLANE) * _SUBLANE))


def choose_block_rows(rows: int, d: int, n_regs: int, itemsize: int) -> int:
    """Pick block_rows: multiple of the 8-sublane tile, working set under
    budget.  n_regs live registers of (block_rows, d) each."""
    denom = max(1, n_regs) * max(d, _LANE) * itemsize
    return clamp_block_rows(max(1, _VMEM_BUDGET // denom), rows)


def _apply_program(prog: Program, blocks, vecs):
    """Unroll the instruction program on VMEM-resident values.

    blocks: dict operand_idx -> (block_rows, d) array for 'full' operands,
            with -1 = main chain... (not used; chain srcs are ('op', i))
    vecs:   dict operand_idx -> (1, d) array
    """
    regs = {}

    def val(src):
        tag, i = src
        return regs[i] if tag == "reg" else blocks[i]

    for ins in prog.instrs:
        op, dst = ins[0], ins[1]
        if op in ("relu", "gelu", "silu", "sigmoid", "tanh", "exp", "copy"):
            x = val(ins[2])
            if op == "relu":
                r = jnp.maximum(x, 0.0)
            elif op == "gelu":
                r = jax.nn.gelu(x)
            elif op == "silu":
                r = x * jax.nn.sigmoid(x)
            elif op == "sigmoid":
                r = jax.nn.sigmoid(x)
            elif op == "tanh":
                r = jnp.tanh(x)
            elif op == "exp":
                r = jnp.exp(x)
            else:
                r = x
        elif op in ("add", "sub", "mul", "div"):
            a, b = val(ins[2]), val(ins[3])
            r = {"add": a + b, "sub": a - b, "mul": a * b,
                 "div": a / b}[op]
        elif op == "scale":
            r = val(ins[2]) * ins[3]
        elif op == "softcap":
            c = ins[3]
            r = jnp.tanh(val(ins[2]) / c) * c
        elif op == "bias":
            r = val(ins[2]) + vecs[ins[3]]
        elif op == "rmsnorm":
            x = val(ins[2]).astype(jnp.float32)
            ms = jnp.mean(x * x, axis=-1, keepdims=True)
            r = (x * jax.lax.rsqrt(ms + ins[4])).astype(val(ins[2]).dtype) \
                * vecs[ins[3]]
        elif op == "layernorm":
            x = val(ins[2]).astype(jnp.float32)
            mu = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
            xn = (x - mu) * jax.lax.rsqrt(var + ins[5])
            r = xn.astype(val(ins[2]).dtype) * vecs[ins[3]] + vecs[ins[4]]
        else:  # pragma: no cover
            raise NotImplementedError(op)
        regs[dst] = r
    return regs[prog.out_reg]


def _kernel(prog: Program, full_idx: Tuple[int, ...], vec_idx: Tuple[int, ...],
            *refs):
    n_full, n_vec = len(full_idx), len(vec_idx)
    full_refs = refs[:n_full]
    vec_refs = refs[n_full:n_full + n_vec]
    out_ref = refs[-1]
    blocks = {i: r[...] for i, r in zip(full_idx, full_refs)}
    vecs = {i: r[...] for i, r in zip(vec_idx, vec_refs)}
    out_ref[...] = _apply_program(prog, blocks, vecs).astype(out_ref.dtype)


def dfp_fused_call(prog: Program, operands: Sequence[jax.Array],
                   out_shape: Tuple[int, ...], out_dtype,
                   block_rows: int = 0,
                   interpret: bool = False) -> jax.Array:
    d = out_shape[-1]
    rows = 1
    for s in out_shape[:-1]:
        rows *= s

    full_idx = tuple(i for i, k in enumerate(prog.operand_kinds)
                     if k == "full")
    vec_idx = tuple(i for i, k in enumerate(prog.operand_kinds) if k == "vec")

    n_regs = len(prog.instrs) + len(full_idx) + 2
    itemsize = jnp.dtype(out_dtype).itemsize
    br = (clamp_block_rows(block_rows, rows) if block_rows
          else choose_block_rows(rows, d, n_regs, itemsize))
    grid = (pl.cdiv(rows, br),)

    full_ops = [operands[i].reshape(rows, d) for i in full_idx]
    vec_ops = [operands[i].reshape(1, d) for i in vec_idx]

    in_specs = (
        [pl.BlockSpec((br, d), lambda r: (r, 0)) for _ in full_ops] +
        [pl.BlockSpec((1, d), lambda r: (0, 0)) for _ in vec_ops])
    out_spec = pl.BlockSpec((br, d), lambda r: (r, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, prog, full_idx, vec_idx),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), out_dtype),
        compiler_params=tpu_params("parallel"),
        interpret=interpret,
    )(*full_ops, *vec_ops)
    return out.reshape(out_shape)
