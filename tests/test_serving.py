"""Serving-subsystem tests: continuous batching through the SOL pipeline.

Covers the ISSUE 5 acceptance surface: scheduler fairness (no request
starves), bucket-padding parity against an unbatched forward at 1e-5,
served elections matching ``impl_report(provenance=True)`` on the same
shapes, the deploy→serve round-trip, and the single-DMA batch staging."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import autotune as AT
from repro.frontends.offload import device
from repro.frontends.optimize import SolModel, optimize
from repro.launch.serve import (ProvenanceError, ServeConfig, SlotArena,
                                SolServer, embedding_table)
from repro.runtime import packed
from repro.runtime.async_queue import AsyncQueue


def tiny_cfg(**kw) -> ServeConfig:
    base = dict(d_model=32, n_heads=2, n_layers=1, vocab=64, max_seq=32,
                max_batch=2, slots=3, backend="xla")
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(autouse=True)
def _native_mode_and_local_cache():
    """Native offload mode + a private autotune cache per test, so serving
    elections never leak into (or read from) the process-wide state other
    tests use."""
    device.set("cpu", 0, mode="native")
    prev = AT.get_cache()
    AT.set_cache(AT.AutotuneCache())
    yield
    AT.set_cache(prev)
    device.set("cpu", 0, mode="native")


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_fairness_no_starvation():
    """5 requests over 3 KV slots and a max_batch of 2: every request
    finishes, and while resident no request waits more than
    ceil(slots/max_batch) steps between serves (LRU round-robin bound)."""
    cfg = tiny_cfg(max_seq=16)
    server = SolServer(cfg)
    reqs = [server.submit([1 + i, 2, 3, 4], max_new_tokens=4)
            for i in range(5)]
    server.run()
    assert server.stats["admitted"] == 5
    assert server.stats["evicted"] == 5
    for r in reqs:
        assert r.done and len(r.generated) == 4
        gaps = np.diff(r.served_steps)
        assert gaps.size == 0 or gaps.max() <= 2, \
            f"request {r.rid} starved: served at steps {r.served_steps}"
    server.close()


def test_prefill_and_decode_interleave():
    """Admission happens mid-stream: a request submitted after serving has
    begun gets a freed/free slot and its prefill shares batches with the
    older requests' decode steps."""
    cfg = tiny_cfg(max_seq=16, slots=3)
    server = SolServer(cfg)
    a = server.submit([1, 2, 3], max_new_tokens=6)
    b = server.submit([4, 5], max_new_tokens=6)
    server.step()                       # both prefill
    late = server.submit([6, 7, 8], max_new_tokens=2)
    server.run()
    assert a.done and b.done and late.done
    # the late request was served while a/b were still decoding
    assert late.served_steps[0] <= max(a.served_steps[-1],
                                       b.served_steps[-1])
    assert server.stats["prefills"] == 3
    assert server.stats["decodes"] == server.stats["tokens"] - 3
    server.close()


def test_admission_blocks_when_slots_full():
    cfg = tiny_cfg(max_seq=16, slots=1, max_batch=2)
    server = SolServer(cfg)
    first = server.submit([1, 2], max_new_tokens=3)
    second = server.submit([3, 4], max_new_tokens=3)
    server.step()
    assert first.phase != "pending" and second.phase == "pending"
    assert server.arena.free_slots == 0
    server.run()
    assert first.done and second.done
    # eviction released the slot for the second request
    assert second.served_steps[0] > first.served_steps[-1]
    server.close()


def test_submit_validation():
    server = SolServer(tiny_cfg())
    with pytest.raises(ValueError):
        server.submit([], 4)
    with pytest.raises(ValueError):
        server.submit(list(range(1, 33)), 4)          # no room to decode
    with pytest.raises(ValueError):
        server.submit([999], 4)                       # out of vocab
    server.close()


# ---------------------------------------------------------------------------
# bucket padding ↔ autotune alignment
# ---------------------------------------------------------------------------

def test_ceil_pow2_buckets_are_their_own_cache_bucket():
    for d in (1, 2, 3, 5, 8, 9, 17, 31, 32, 33, 100):
        p = AT.ceil_pow2(d)
        assert p >= d and (p & (p - 1)) == 0
        assert AT.bucket_dim(p) == p        # pow2 is its own bucket
    assert AT.pad_shape((3, 11, 32)) == (4, 16, 32)


def test_bucket_padding_parity_vs_unbatched_forward():
    """A prompt of length 11 served through the padded (1, 16) bucket must
    produce the same next-token logits as an unpadded, unbatched (1, 11)
    forward through the same pipeline — at 1e-5."""
    cfg = tiny_cfg(max_batch=1, slots=1)
    server = SolServer(cfg)
    prompt = (np.arange(1, 12) % cfg.vocab).astype(np.int32)
    req = server.submit(prompt, max_new_tokens=1)
    server.run()
    assert req.done and req.last_logits is not None
    assert "1x16" in server.stats["buckets"]          # served padded

    x = embedding_table(cfg)[prompt][None]            # (1, 11, d_model)
    sol = optimize(server.model, (1, len(prompt), cfg.d_model),
                   backend=cfg.backend)
    ref = np.asarray(sol(jnp.asarray(x)))[0, -1]
    np.testing.assert_allclose(req.last_logits, ref, rtol=1e-5, atol=1e-5)
    server.close()


# ---------------------------------------------------------------------------
# elections + provenance
# ---------------------------------------------------------------------------

def test_served_elections_match_impl_report_with_measured_provenance():
    cfg = tiny_cfg()
    server = SolServer(cfg, strict_provenance=True)
    for i in range(3):
        server.submit([i + 1, 2, 3, 4, 5], max_new_tokens=3)
    counts = server.warm_autotune()
    assert counts["impls"] > 0
    server.run()
    assert server.served_elections
    for bucket, rec in server.served_elections.items():
        model = server._models[bucket]
        assert isinstance(model, SolModel)
        assert model.check_provenance() == []
        rep = model.impl_report(by_kind=True)
        prov = model.impl_report(provenance=True)
        for kind, impls in rec["by_op"].items():
            assert rep[kind] == impls, \
                f"served elections diverge from impl_report for {kind}"
            for name in impls:
                assert set(prov[name]["sources"]) == {"measured"}
    server.close()


def test_strict_provenance_cold_cache_is_loud():
    """With an empty autotune cache a strict server must refuse to serve —
    the 'silent roofline fallback' the smoke run exists to catch."""
    server = SolServer(tiny_cfg(), strict_provenance=True)
    server.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(ProvenanceError, match="unmeasured"):
        server.run()
    server.close()


def test_strict_provenance_rejects_nearest_bucket_fallback():
    """'measured' provenance via the cache's nearest-bucket fallback is
    timings from a DIFFERENT shape: a strict server must refuse a bucket
    whose exact shapes were never measured, even when nearby buckets were
    — and an incremental re-warm (which skips covered buckets) unblocks."""
    cfg = tiny_cfg()
    server = SolServer(cfg, strict_provenance=True)
    server.submit([1, 2, 3, 4], max_new_tokens=2)
    server.warm_autotune()                   # covers seq bucket 8 only
    server.submit(list(range(1, 13)), max_new_tokens=2)   # opens seq 16
    with pytest.raises(ProvenanceError, match="nearest-bucket"):
        server.run()
    again = server.warm_autotune()           # warm the new bucket only
    assert again["nodes"] > 0 and again["skipped"] > 0
    server.run()
    assert all(r.done for r in server._finished)
    server.close()


def test_warm_autotune_skips_already_measured_buckets():
    cfg = tiny_cfg()
    server = SolServer(cfg)
    server.submit([1, 2, 3, 4], max_new_tokens=2)
    first = server.warm_autotune(warmup=0, iters=1)
    again = server.warm_autotune(warmup=0, iters=1)
    assert first["nodes"] > 0
    assert again["nodes"] == 0 and again["skipped"] >= first["nodes"]
    server.close()


# ---------------------------------------------------------------------------
# deploy → serve round-trip
# ---------------------------------------------------------------------------

def test_deploy_serve_roundtrip():
    cfg = tiny_cfg(max_seq=16, max_batch=2, slots=2)
    live = SolServer(cfg)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    live_reqs = [live.submit(p, max_new_tokens=3) for p in prompts]
    live.run()
    arts = live.export_artifacts()
    assert arts, "live serving compiled no bucket models?"
    assert all(isinstance(b, bytes) for b in arts.values())

    replay = SolServer(cfg, deployed=arts)
    rep_reqs = [replay.submit(p, max_new_tokens=3) for p in prompts]
    replay.run()
    for a, b in zip(live_reqs, rep_reqs):
        assert a.generated == b.generated, \
            f"artifact serving diverged for request {a.rid}"
    # the artifact's election metadata mirrors the live model's report
    for bucket in arts:
        assert (replay._models[bucket].impl_report(by_kind=True)
                == live._models[bucket].impl_report(by_kind=True))
    # a bucket without an artifact is loud, never a silent live compile
    with pytest.raises(KeyError, match="deploy"):
        replay._model_for((8, 8))
    live.close()
    replay.close()


# ---------------------------------------------------------------------------
# staging + arena
# ---------------------------------------------------------------------------

def test_stage_batch_is_one_dma():
    packed.reset_transfer_stats()
    rows = [np.full((8, 4), i, np.float32) for i in range(3)]
    x = packed.stage_batch(rows)
    assert x.shape == (3, 8, 4)
    for i in range(3):
        assert float(np.asarray(x)[i, 0, 0]) == i
    assert packed.TRANSFER_STATS["packed_dmas"] == 1
    assert packed.TRANSFER_STATS["direct_dmas"] == 0
    with pytest.raises(ValueError, match="uniform"):
        packed.stage_batch([np.zeros((2,)), np.zeros((3,))])
    with pytest.raises(ValueError):
        packed.stage_batch([])


def test_serving_uses_one_dma_per_forward():
    """Each program dispatch stages its whole input set as one DMA: a step
    that runs both a prefill and a decode forward issues exactly two."""
    cfg = tiny_cfg(max_seq=16)
    server = SolServer(cfg)
    for i in range(3):
        server.submit([i + 1, 2, 3], max_new_tokens=2)
    packed.reset_transfer_stats()
    summary = server.run()
    assert summary["dmas"] == summary["forwards"]
    assert summary["forwards"] >= summary["steps"]
    assert (packed.TRANSFER_STATS["packed_dmas"]
            + packed.TRANSFER_STATS["direct_dmas"]) == summary["dmas"]
    server.close()


def test_reforward_baseline_uses_one_dma_per_step():
    """The decode=False baseline keeps the old invariant: one mixed-phase
    forward, one packed DMA, per scheduler step."""
    cfg = tiny_cfg(max_seq=16, decode=False)
    server = SolServer(cfg)
    for i in range(3):
        server.submit([i + 1, 2, 3], max_new_tokens=2)
    packed.reset_transfer_stats()
    summary = server.run()
    assert summary["mode"] == "reforward"
    assert summary["dmas"] == summary["steps"] == summary["forwards"]
    assert packed.TRANSFER_STATS["packed_dmas"] == summary["steps"]
    server.close()


def test_slot_arena_admission_eviction_and_pointer_append():
    q = AsyncQueue()
    arena = SlotArena(q, n_slots=2, max_seq=8)
    s0 = arena.admit(np.asarray([5, 6, 7], np.int32))
    s1 = arena.admit(np.asarray([9], np.int32))
    assert arena.admit(np.asarray([1], np.int32)) is None   # full
    arena.append(s0, 42)
    q.synchronize()
    assert arena.tokens(s0).tolist() == [5, 6, 7, 42]
    assert arena.tokens(s1).tolist() == [9]
    arena.evict(s1)
    s2 = arena.admit(np.asarray([2, 3], np.int32))          # slot reused
    assert s2 is not None
    q.synchronize()
    assert arena.tokens(s2).tolist() == [2, 3]
    q.close()


def test_slot_arena_rejects_oversized_prompt():
    q = AsyncQueue()
    arena = SlotArena(q, n_slots=1, max_seq=4)
    with pytest.raises(ValueError, match="exceeds"):
        arena.admit(np.arange(5, dtype=np.int32))
    assert arena.free_slots == 1       # nothing leaked
    q.close()


# ---------------------------------------------------------------------------
# incremental decode program (ISSUE 6 tentpole)
# ---------------------------------------------------------------------------

def test_decode_program_matches_reforward_baseline():
    """The incremental decode path (prefill seeds the KV slots, then one
    DECODE_ATTENTION token step per tick) must reproduce the full
    re-forward baseline token-for-token, and its final-step logits to
    1e-5 — same workload, same greedy sampling, two schedulers."""
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    from repro.launch.serve import build_lm
    model = build_lm(tiny_cfg(max_seq=16))     # ONE weight init, two paths
    runs = {}
    for decode in (True, False):
        cfg = tiny_cfg(max_seq=16, decode=decode)
        server = SolServer(cfg, model)
        reqs = [server.submit(p, max_new_tokens=4) for p in prompts]
        server.run()
        runs[decode] = reqs
        server.close()
    for a, b in zip(runs[True], runs[False]):
        assert a.generated == b.generated, \
            f"decode path diverged for request {a.rid}"
        np.testing.assert_allclose(a.last_logits, b.last_logits,
                                   rtol=1e-5, atol=1e-5)


def test_decode_buckets_and_elections():
    """Decode steps run through (batch, cache)-bucketed decode programs
    whose elections include the DECODE_ATTENTION op — the decode forward
    never silently falls back to the full program."""
    cfg = tiny_cfg(max_seq=32)
    server = SolServer(cfg)
    server.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=8)
    summary = server.run()
    assert summary["mode"] == "decode"
    assert any(k.startswith("d") for k in summary["buckets"]), \
        f"no decode buckets served: {summary['buckets']}"
    decode_keys = [k for k in server._models if k[0] == "decode"]
    assert decode_keys
    for key in decode_keys:
        by_op = server.served_elections[key]["by_op"]
        assert "decode_attention" in by_op, \
            f"decode bucket {key} elected no DECODE_ATTENTION impl"
    # prefill ran exactly once per request; every other token was O(1)
    assert summary["prefills"] == 1
    assert summary["decodes"] == summary["tokens"] - 1
    server.close()


def test_decode_input_size_is_cache_bucket_not_history():
    """O(1)-per-token structurally: the decode program's input bytes are a
    function of the CACHE bucket, not of how many steps already ran — the
    re-forward baseline's per-step bytes instead grow with the context."""
    cfg = tiny_cfg(max_seq=32)
    server = SolServer(cfg)
    server.submit([1, 2, 3], max_new_tokens=12)
    sizes = []
    orig = packed.stage_inputs

    def spy(arrays, device=None):
        sizes.append(sum(a.nbytes for a in arrays))
        return orig(arrays, device)

    packed.stage_inputs = spy
    try:
        server.run()
    finally:
        packed.stage_inputs = orig
    # first token came from prefill; the other 11 are one decode DMA each
    assert len(sizes) == 11
    # within one cache bucket the staged bytes are constant
    assert len(set(sizes[:4])) == 1, sizes      # cache lens 3..6 → cb 8
    server.close()


def test_slot_arena_kv_regions_pointer_append_and_gather():
    q = AsyncQueue()
    arena = SlotArena(q, n_slots=2, max_seq=4,
                      kv_row_shapes=[(2, 3), (2, 3)])
    s = arena.admit(np.asarray([7], np.int32))
    rows0 = np.arange(12, dtype=np.float32).reshape(2, 2, 3)
    arena.write_kv_rows(s, 0, 0, rows0)               # seed rows [0, 2)
    arena.write_kv_rows(s, 1, 1, rows0[:1] + 100.0)   # append at row 1
    q.synchronize()
    np.testing.assert_array_equal(arena.kv_rows(s, 0, 2), rows0)
    np.testing.assert_array_equal(arena.kv_rows(s, 1, 2)[1],
                                  rows0[0] + 100.0)
    with pytest.raises(ValueError, match="overflows"):
        arena.write_kv_rows(s, 0, 3, rows0)           # rows [3, 5) > max 4
    arena.evict(s)
    s2 = arena.admit(np.asarray([1], np.int32))       # regions recycled
    assert s2 is not None
    q.synchronize()
    q.close()


def test_decode_step_stages_token_rows_lens_and_slot_ids_only():
    """A decode step sends its token rows, cache lengths and slot ids
    host→device and brings back its logits; the cache rows stay on the
    device."""
    cfg = tiny_cfg(max_seq=32, max_batch=2)
    server = SolServer(cfg)
    server.submit([1, 2, 3], max_new_tokens=6)
    server.submit([4, 5, 6, 7, 8], max_new_tokens=6)
    server.step()                                   # both prefill
    db = 2
    staged = db * cfg.d_model * 4 + db * 4 + db * 4     # x, lens, slot ids
    for _ in range(4):
        h2d, d2h = packed.TRANSFER_STATS["bytes"], server.stats["d2h_bytes"]
        server.step()
        assert packed.TRANSFER_STATS["bytes"] - h2d == staged
        assert server.stats["d2h_bytes"] - d2h == db * cfg.vocab * 4
    assert server.stats["decodes"] == 4 * db
    assert server.stats["kv_host_bytes"] == 0
    server.close()


def test_slot_reuse_reads_no_stale_rows():
    """A short request that takes the slot of a longer, evicted one serves
    the same tokens and logits as on a fresh server: the earlier tenant's
    rows past its length weigh nothing."""
    from repro.launch.serve import build_lm
    cfg = tiny_cfg(max_seq=32, slots=1, max_batch=1)
    model = build_lm(cfg)
    reused = SolServer(cfg, model)
    reused.submit(list(range(1, 21)), max_new_tokens=6)
    short = reused.submit([7, 8, 9], max_new_tokens=4)
    reused.run()
    # the long request's rows are still there past the short one's cache
    assert np.abs(reused.arena.kv_rows(0, 0, 20)[short.length - 1:]).sum() > 0
    fresh = SolServer(cfg, model)
    alone = fresh.submit([7, 8, 9], max_new_tokens=4)
    fresh.run()
    assert short.generated == alone.generated
    np.testing.assert_array_equal(short.last_logits, alone.last_logits)
    reused.close()
    fresh.close()


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_COMPILES = {"n": 0, "listening": False}


def _compile_count() -> int:
    """JAX compile events so far, counted as the benchmark harness counts
    them (a process-wide listener, registered once)."""
    import jax
    if not _COMPILES["listening"]:
        def on_event(event, _duration, **_kw):
            if event in _COMPILE_EVENTS:
                _COMPILES["n"] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        _COMPILES["listening"] = True
    return _COMPILES["n"]


def test_warmed_buckets_serve_without_compiling():
    """Once every bucket of a small plan has been opened as a prefill and
    as a decode, serving them with 1..max_batch real rows compiles nothing:
    the cache gather and writes are shaped by the bucket alone."""
    cfg = tiny_cfg(max_seq=32, max_batch=4, slots=4)
    server = SolServer(cfg)
    rng = np.random.default_rng(0)
    for b in (1, 2, 4):
        for s in (8, 16, 32):
            for _ in range(b):        # prefill at (b, s), decode at (b, s)
                server.submit(rng.integers(1, cfg.vocab, s - 2), 2)
            server.run()
    before = _compile_count()
    for n in range(1, cfg.max_batch + 1):
        for _ in range(n):
            server.submit(rng.integers(1, cfg.vocab, rng.integers(3, 20)),
                          int(rng.integers(2, 10)))
        server.run()
    assert _compile_count() == before
    assert server.stats["decode_real"] < server.stats["decode_rows"]  # padded
    server.close()


def test_kv_rows_written_counts_prompt_and_decoded_rows():
    """Every prompt position and every decoded token's row is written on
    the device once, and no cache byte crosses to the host."""
    cfg = tiny_cfg(max_seq=32)
    server = SolServer(cfg)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]]
    for p in prompts:
        server.submit(p, max_new_tokens=5)
    server.run()
    st = server.stats
    assert st["decodes"] == 3 * 4
    assert st["kv_rows_written"] == sum(map(len, prompts)) + st["decodes"]
    assert st["kv_host_bytes"] == 0
    server.close()


# ---------------------------------------------------------------------------
# sampling determinism (ISSUE 6 satellite)
# ---------------------------------------------------------------------------

def _run_sampling(cfg, model, sampling):
    server = SolServer(cfg, model)
    reqs = [server.submit([3, 1, 4, 1], max_new_tokens=5,
                          sampling=sampling),
            server.submit([2, 7, 1], max_new_tokens=5, sampling=sampling)]
    server.run()
    server.close()
    return [r.generated for r in reqs]


def test_sampling_same_seed_is_identical_across_runs():
    from repro.launch.serve import SamplingParams, build_lm
    cfg = tiny_cfg(max_seq=16)
    model = build_lm(cfg)
    sp = SamplingParams(temperature=0.8, top_k=8, top_p=0.9, seed=123)
    assert _run_sampling(cfg, model, sp) == _run_sampling(cfg, model, sp)


def test_sampling_live_vs_deployed_identical():
    """Temperature sampling replayed through deployed artifacts must
    reproduce the live tokens exactly: same logits bits, same per-request
    seeded generator."""
    from repro.launch.serve import SamplingParams
    cfg = tiny_cfg(max_seq=16)
    sp = SamplingParams(temperature=0.7, top_p=0.95, seed=42)
    live = SolServer(cfg)
    live_reqs = [live.submit([5, 6, 7], max_new_tokens=4, sampling=sp),
                 live.submit([8, 9], max_new_tokens=4, sampling=sp)]
    live.run()
    replay = SolServer(cfg, deployed=live.export_artifacts())
    rep_reqs = [replay.submit([5, 6, 7], max_new_tokens=4, sampling=sp),
                replay.submit([8, 9], max_new_tokens=4, sampling=sp)]
    replay.run()
    for a, b in zip(live_reqs, rep_reqs):
        assert a.generated == b.generated
    live.close()
    replay.close()


def test_sampling_edge_cases_reduce_to_greedy_and_full_mass():
    from repro.launch.serve import SamplingParams, sample_token
    rng = np.random.default_rng(0)
    logits = np.asarray([0.1, 2.5, -1.0, 0.4], np.float32)
    # top_k=1 keeps only the argmax regardless of temperature
    sp1 = SamplingParams(temperature=1.3, top_k=1, seed=0)
    for _ in range(5):
        assert sample_token(logits, sp1, rng) == int(np.argmax(logits))
    # top_p=1.0 is plain temperature sampling: same seed → same token
    spa = SamplingParams(temperature=0.9, top_p=1.0, seed=5)
    ta = sample_token(logits, spa, np.random.default_rng(5))
    tb = sample_token(logits, spa, np.random.default_rng(5))
    assert ta == tb
    # temperature<=0 is greedy and consumes no randomness
    assert sample_token(logits, SamplingParams(), None) \
        == int(np.argmax(logits))


def test_sampling_params_validation():
    from repro.launch.serve import SamplingParams
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
