"""End-to-end behaviour tests: training improves loss across architecture
families; the optimizer/step machinery composes; HLO analysis is sane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data import DataConfig, SyntheticTokenDataset
from repro.distributed.steps import (StepOptions, init_train_state,
                                     make_train_step)
from repro.launch.mesh import make_debug_mesh
from repro.models import backbone as B


def _run_training(arch, steps=12, microbatch=1, compression="none"):
    cfg = get_smoke(arch)
    mesh = make_debug_mesh(1, 1)
    opts = StepOptions(remat=False, microbatch=microbatch,
                       grad_compression=compression, zero=False,
                       lr=3e-3, warmup=2, total_steps=steps)
    step_fn, _ = make_train_step(mesh, cfg, opts)
    state = init_train_state(cfg, opts, jax.random.PRNGKey(0))
    ds = SyntheticTokenDataset(DataConfig(seed=0, vocab=cfg.vocab,
                                          seq_len=32, global_batch=4))
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    losses = []
    with mesh:
        for step in range(steps):
            batch = {k: jnp.asarray(v) for k, v in ds.batch(step).items()}
            state, metrics = jitted(state, batch)
            losses.append(float(metrics["loss"]))
    return losses


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "rwkv6-1.6b",
                                  "recurrentgemma-9b", "olmoe-1b-7b"])
def test_training_improves_loss(arch):
    losses = _run_training(arch)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


def test_microbatch_accumulation_consistent():
    """Grad accumulation (4 microbatches) tracks the single-batch step."""
    l1 = _run_training("qwen2-1.5b", steps=8, microbatch=1)
    l4 = _run_training("qwen2-1.5b", steps=8, microbatch=4)
    assert all(np.isfinite(l4))
    assert abs(l1[0] - l4[0]) < 0.2          # same init, same first loss-ish
    assert np.mean(l4[-2:]) < l4[0]


def test_bf16_grad_compression_trains():
    losses = _run_training("qwen2-1.5b", steps=8, compression="bf16")
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_hlo_analysis_counts_scan_trips():
    """The loop-aware analyzer must multiply while-body costs by the scan
    trip count (the builtin cost_analysis does not)."""
    from repro.launch.hlo_analysis import analyze

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    ws = jnp.zeros((7, 64, 64))
    x = jnp.zeros((8, 64))
    text = jax.jit(f).lower(ws, x).compile().as_text()
    res = analyze(text, 1)
    expected = 2 * 8 * 64 * 64 * 7            # 7 scanned matmuls
    assert res["flops_per_device"] >= expected * 0.99
    assert any(l["trips"] == 7 for l in res["loops"])


def test_adamw_decreases_quadratic():
    from repro.optim import AdamWConfig, adamw_update, init_opt_state
    ocfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = init_opt_state(params, ocfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(params, grads, state, ocfg,
                                        jnp.asarray(0.1))
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_checkpoint_resume_training(tmp_path):
    """Stop training mid-way, restore, continue — bitwise state shape
    integrity and loss continuity."""
    from repro.checkpoint import CheckpointManager
    cfg = get_smoke("qwen2-1.5b")
    mesh = make_debug_mesh(1, 1)
    opts = StepOptions(remat=False, zero=False, lr=1e-3, warmup=1,
                       total_steps=10)
    step_fn, _ = make_train_step(mesh, cfg, opts)
    state = init_train_state(cfg, opts, jax.random.PRNGKey(0))
    ds = SyntheticTokenDataset(DataConfig(seed=0, vocab=cfg.vocab,
                                          seq_len=16, global_batch=2))
    ckpt = CheckpointManager(str(tmp_path), interval=3)
    jitted = jax.jit(step_fn)
    with mesh:
        for step in range(6):
            batch = {k: jnp.asarray(v) for k, v in ds.batch(step).items()}
            state, m = jitted(state, batch)
            ckpt.maybe_save(step + 1, state, block=True)
    restored_step, restored = ckpt.restore_latest(
        jax.eval_shape(lambda: state))
    assert restored_step == 6
    np.testing.assert_allclose(
        np.asarray(restored["params"]["embed"], np.float32),
        np.asarray(state["params"]["embed"], np.float32))
    with mesh:
        batch = {k: jnp.asarray(v) for k, v in ds.batch(6).items()}
        state2, m2 = jitted(restored, batch)
    assert np.isfinite(float(m2["loss"]))


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_placed_by_entry_point_helper(monkeypatch, tmp_path,
                                                    env_set):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX to use it and
    sets no other directory; without it the cache goes to one fixed,
    git-ignored directory at the root of the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert CC.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            root = Path(__file__).resolve().parents[1]
            assert CC.CHECKOUT_CACHE == root / ".jax_cache"
            assert CC.use_compile_cache() == str(CC.CHECKOUT_CACHE)
            assert jax.config.jax_compilation_cache_dir == str(
                CC.CHECKOUT_CACHE)
            assert ".jax_cache/" in (root / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
