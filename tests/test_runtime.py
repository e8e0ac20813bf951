"""SOL runtime tests: async queue semantics, virtual-pointer arithmetic
(the paper's 32+32-bit encoding), packed memcopies."""
import threading

from _hypo import hypothesis, st  # real hypothesis, or skip-stubs when absent
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import (AsyncQueue, UseAfterFreeError, VirtualAllocator,
                           VirtualPtr, pack_transfer, unpack_on_device)
from repro.runtime.packed import transfer


def test_virtual_ptr_encoding():
    p = VirtualPtr((3 << 32) | 100)
    assert p.ref == 3 and p.offset == 100
    q = p + 28
    assert q.ref == 3 and q.offset == 128     # arithmetic keeps the ref
    r = q - 128
    assert r.offset == 0


def test_virtual_ptr_offset_range():
    p = VirtualPtr(1 << 32)
    with pytest.raises(ValueError):
        _ = p + (1 << 32)                      # overflows 32-bit offset


def test_async_malloc_is_nonblocking_and_ordered():
    q = AsyncQueue()
    ptr = q.malloc_async(1024)                 # returns immediately
    assert isinstance(ptr, VirtualPtr)
    src = np.arange(256, dtype=np.float32)
    q.memcpy_async(ptr, src)
    q.synchronize()
    buf = q.allocator.resolve(ptr)[:src.nbytes]
    np.testing.assert_array_equal(buf.view(np.float32), src)
    q.free_async(ptr)
    q.synchronize()
    assert q.allocator.live_refs == 0
    stats = q.stats()
    assert stats["executed"] >= 4              # malloc, memcpy, free, syncs
    q.close()


def test_async_queue_pointer_arithmetic_before_materialization():
    """The paper's point: the virtual pointer participates in arithmetic
    while the allocation has not happened yet."""
    q = AsyncQueue()
    ptr = q.malloc_async(4096)
    sub = ptr + 1024                           # arithmetic pre-materialize
    q.memcpy_async(sub, np.full(16, 7, np.uint8))
    q.synchronize()
    assert (q.allocator.resolve(ptr)[1024:1040] == 7).all()
    q.close()


# -- ISSUE 5 regression tests: async-runtime correctness ----------------------

def test_async_queue_worker_survives_exception_and_reraises():
    """A failing queued op must not kill the worker thread: the queue keeps
    draining (no deadlocked synchronize) and the stored error is re-raised
    on the NEXT synchronize, CUDA-style."""
    q = AsyncQueue()
    q.launch(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        q.synchronize()
    # the worker is still alive: later work executes and syncs cleanly
    ptr = q.malloc_async(64)
    q.memcpy_async(ptr, np.arange(16, dtype=np.uint8))
    q.synchronize()                        # error already consumed
    assert (q.allocator.resolve(ptr)[:16] == np.arange(16)).all()
    assert q.stats()["errors"] == 1
    q.close()


def test_async_queue_close_never_hangs_or_raises():
    q = AsyncQueue()

    def boom():
        raise RuntimeError("kernel failed")

    q.launch(boom)
    q.close()                              # drains; neither hangs nor raises
    assert isinstance(q.pending_error(), RuntimeError)


def test_memcpy_async_snapshots_source_at_enqueue():
    """Mutating the source AFTER enqueue must not corrupt the transfer.
    The worker is parked on an event so the pre-fix by-reference capture
    would deterministically read the mutated bytes."""
    q = AsyncQueue()
    gate = threading.Event()
    q.launch(gate.wait)                    # park the worker
    ptr = q.malloc_async(64)
    src = np.arange(16, dtype=np.uint8)
    q.memcpy_async(ptr, src)
    src[:] = 0                             # mutate after enqueue
    gate.set()
    q.synchronize()
    assert (q.allocator.resolve(ptr)[:16] == np.arange(16)).all()
    q.close()


def test_use_after_free_is_loud():
    a = VirtualAllocator()
    p = a.malloc(32)
    a.free(p)
    with pytest.raises(UseAfterFreeError, match=str(p.ref)):
        a.resolve(p)
    with pytest.raises(UseAfterFreeError):
        a.materialize(p)
    with pytest.raises(UseAfterFreeError):
        a.free(p)                          # double free is loud too
    with pytest.raises(UseAfterFreeError):
        a.free(VirtualPtr(999 << 32))      # never-allocated ref


def test_async_use_after_free_surfaces_at_synchronize():
    q = AsyncQueue()
    ptr = q.malloc_async(32)
    q.free_async(ptr)
    q.memcpy_async(ptr, np.zeros(4, np.uint8))   # executes after the free
    with pytest.raises(UseAfterFreeError):
        q.synchronize()
    q.close()


@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(
    shapes=st.lists(
        st.tuples(st.integers(1, 8), st.integers(1, 16)),
        min_size=1, max_size=8),
    dtype=st.sampled_from([np.float32, np.int32, np.float16]),
    seed=st.integers(0, 1000))
def test_packed_transfer_roundtrip(shapes, dtype, seed):
    rng = np.random.default_rng(seed)
    arrays = [rng.standard_normal(s).astype(dtype) for s in shapes]
    pt = pack_transfer(arrays)
    out = unpack_on_device(pt)
    assert len(out) == len(arrays)
    for a, o in zip(arrays, out):
        np.testing.assert_array_equal(np.asarray(o), a)


def test_packed_buffer_words_follow_the_narrowest_item():
    """32-bit arrays travel in a uint32 buffer, unpacked by same-width
    bitcasts; a byte array among them makes the buffer bytes."""
    wide = [np.ones((3, 5), np.float32), np.arange(3, dtype=np.int32)]
    assert pack_transfer(wide).buffer.dtype == np.uint32
    assert pack_transfer(wide + [np.ones(3, np.uint8)]).buffer.dtype \
        == np.uint8
    out = unpack_on_device(pack_transfer(wide))
    for a, o in zip(wide, out):
        np.testing.assert_array_equal(np.asarray(o), a)


def test_packed_alignment():
    arrays = [np.ones(3, np.uint8), np.ones(5, np.float32)]
    pt = pack_transfer(arrays)
    for _, _, off in pt.layout:
        assert off % 128 == 0                  # lane-aligned segments


def test_transfer_policy_split():
    small = [np.ones(2, np.float32)]
    out = transfer(small)                      # latency path
    np.testing.assert_array_equal(np.asarray(out[0]), small[0])
    many = [np.full((64, 64), i, np.float32) for i in range(8)]
    out = transfer(many)                       # packed path
    for i, o in enumerate(out):
        assert float(np.asarray(o)[0, 0]) == i
