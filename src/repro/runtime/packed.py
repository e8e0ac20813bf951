"""Packed memcopies (paper Sec. IV-C, the VEO-udma mechanism).

"We gather multiple adjacent memcopies and group them together … many small
tensors can be packed into a big data segment to speed up transfers."

JAX analogue: many small host arrays (e.g. the dozens of norm gains /
biases of a model, or a serving request batch) are flattened into ONE
contiguous staging buffer, moved with a single ``jax.device_put`` (one DMA
instead of N), and re-sliced on device with zero-copy ``lax.dynamic_slice``
views.  Below a size threshold the latency-optimized direct path is used —
exactly the paper's policy split.

Mesh serving: every ``device=`` parameter below is a ``jax.device_put``
target, so it accepts a ``Sharding`` as well as a single device.  The
mesh-mode server passes ``NamedSharding(mesh, P())`` (see
:func:`replicated`): the packed buffer broadcasts to every shard as one
host→device DMA, and the per-spec layout (batch split across ``data``,
heads across ``model``) happens device-to-device when the sharded
executable consumes the inputs — host staging stays a single gather
exactly as on one device."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .telemetry import span

LATENCY_THRESHOLD_BYTES = 1 << 14     # small transfers go direct

# Process-wide transfer accounting: how many DMAs (packed vs direct) the
# policy issued and how many host bytes crossed.  The serving scheduler and
# the staged-exactly-once deployment test read these.
TRANSFER_STATS = {"packed_dmas": 0, "direct_dmas": 0, "bytes": 0}


def reset_transfer_stats() -> Dict[str, int]:
    prev = dict(TRANSFER_STATS)
    TRANSFER_STATS.update(packed_dmas=0, direct_dmas=0, bytes=0)
    return prev


def replicated(mesh) -> Any:
    """The mesh-mode staging target: one packed buffer, broadcast to every
    shard (fully-replicated NamedSharding) — the single-DMA policy's
    closest analogue when 'the device' is a mesh."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


@dataclasses.dataclass
class PackedTransfer:
    buffer: jax.Array                  # packed staging buffer, uint words
    layout: List[Tuple[Tuple[int, ...], str, int]]  # (shape, dtype, offset)


def pack_transfer(arrays: Sequence[np.ndarray],
                  device=None) -> PackedTransfer:
    """Pack many host arrays into one device transfer.  The buffer's words
    are as wide as the narrowest array's items, at most 32 bits: a TPU
    unpacks a 32-bit buffer of 32-bit arrays with same-width bitcasts,
    where a byte buffer needs a 4-wide minor dimension that the compiler
    pads to 128 lanes (and, for some layouts of a few MB, compiles for
    minutes)."""
    layout: List[Tuple[Tuple[int, ...], str, int]] = []
    total = 0
    aligned: List[np.ndarray] = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        off = (total + 127) & ~127     # 128-byte alignment (lane-friendly)
        layout.append((tuple(a.shape), str(a.dtype), off))
        total = off + a.nbytes
        aligned.append(a)
    word = min([4] + [a.itemsize for a in aligned])
    with span("sol.stage.pack"):
        buf = np.zeros(-(-total // word) * word, np.uint8)
        for a, (_, _, off) in zip(aligned, layout):
            buf[off:off + a.nbytes] = a.view(np.uint8).reshape(-1)
    with span("sol.stage.put"):
        dev_buf = jax.device_put(buf.view(f"uint{8 * word}"), device)
    return PackedTransfer(dev_buf, layout)


def unpack_on_device(pt: PackedTransfer) -> List[jax.Array]:
    """Zero-copy-ish on-device reslicing of the packed buffer.  The reslice
    of a whole layout is ONE jitted dispatch, cached per layout — a serving
    bucket pays the trace once and every subsequent step's unpack is a
    single executable call instead of 2·N eager ops."""
    return list(_unpack_jit(tuple(pt.layout))(pt.buffer))


@functools.lru_cache(maxsize=512)
def _unpack_jit(layout: Tuple[Tuple[Tuple[int, ...], str, int], ...]):
    def f(buf):
        word = buf.dtype.itemsize
        out = []
        with jax.named_scope("sol.unpack"):
            for shape, dtype, off in layout:
                item = np.dtype(dtype).itemsize
                n = int(np.prod(shape)) * item // word
                if n == 0:
                    out.append(jnp.zeros(shape, dtype))
                    continue
                chunk = jax.lax.dynamic_slice(buf, (off // word,), (n,))
                if item > word:
                    # bitcast folds the trailing (item / word) dim
                    chunk = chunk.reshape(-1, item // word)
                arr = jax.lax.bitcast_convert_type(chunk, jnp.dtype(dtype))
                out.append(arr.reshape(shape))
        return out
    return jax.jit(f)


def transfer(arrays: Sequence[np.ndarray], device=None) -> List[jax.Array]:
    """Policy split: small singletons direct (latency-optimized); batches of
    small tensors packed (bandwidth-optimized)."""
    total = sum(a.nbytes for a in arrays)
    TRANSFER_STATS["bytes"] += total
    if len(arrays) == 1 or total < LATENCY_THRESHOLD_BYTES:
        TRANSFER_STATS["direct_dmas"] += len(arrays)
        return [jax.device_put(a, device) for a in arrays]
    TRANSFER_STATS["packed_dmas"] += 1
    return unpack_on_device(pack_transfer(arrays, device))


def stage_inputs(arrays: Sequence[np.ndarray], device=None) -> List[jax.Array]:
    """Stage a heterogeneous input set host→device as ONE packed DMA.

    The serving decode step feeds one forward several arrays of different
    shapes and dtypes — token rows (f32), per-request cache lengths (int32)
    and slot ids (int32).  They are consumed together by one step, so like
    :func:`stage_batch` they are a bandwidth object regardless of size:
    always one packed segment, resliced on device, never N direct puts."""
    if not arrays:
        raise ValueError("stage_inputs needs at least one array")
    arrays = [np.ascontiguousarray(a) for a in arrays]
    nbytes = sum(a.nbytes for a in arrays)
    TRANSFER_STATS["bytes"] += nbytes
    TRANSFER_STATS["packed_dmas"] += 1
    with span("sol.stage", bytes=nbytes):
        return unpack_on_device(pack_transfer(arrays, device))


def stage_batch(rows: Sequence[np.ndarray], device=None) -> jax.Array:
    """Stage a serving batch host→device as ONE DMA and stack on device.

    Every row must share shape and dtype (the scheduler has already padded
    them to a common bucket).  Unlike :func:`transfer`, a multi-row batch is
    ALWAYS gathered into one packed segment — the batch is about to be
    consumed as a single tensor, so it is a bandwidth object even when it
    is small (the paper's VEO-udma policy applied to request batches) —
    and the stack is a device-side reslice of the packed buffer."""
    if not rows:
        raise ValueError("stage_batch needs at least one row")
    rows = [np.ascontiguousarray(r) for r in rows]
    shapes = {r.shape for r in rows}
    if len(shapes) > 1 or len({str(r.dtype) for r in rows}) > 1:
        raise ValueError(
            f"stage_batch needs uniform rows, got shapes "
            f"{sorted(shapes)} — pad to a common bucket first")
    nbytes = sum(r.nbytes for r in rows)
    TRANSFER_STATS["bytes"] += nbytes
    with span("sol.stage", bytes=nbytes):
        if len(rows) == 1:
            TRANSFER_STATS["direct_dmas"] += 1
            with span("sol.stage.put"):
                row = jax.device_put(rows[0], device)
            return jnp.stack([row])
        TRANSFER_STATS["packed_dmas"] += 1
        return jnp.stack(unpack_on_device(pack_transfer(rows, device)))
