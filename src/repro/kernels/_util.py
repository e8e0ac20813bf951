"""Shared kernel helpers: the padding/alignment convention and the VMEM
budget every kernel compiles under live here once."""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM each pallas_call is compiled with, and the budget every
# ``Tunable`` gate and ``supports`` predicate sizes its working set against.
# Mosaic's default scoped limit (16 MiB on v5e) is far below the chip's
# 128 MiB of VMEM; half of it is requested explicitly, leaving the rest to
# XLA's own fusions around the kernel.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return -(-x // m) * m


def tpu_params(*semantics: str) -> pltpu.CompilerParams:
    """Mosaic compiler parameters: one ``parallel``/``arbitrary`` entry per
    grid axis, compiled under :data:`VMEM_LIMIT_BYTES`."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)
