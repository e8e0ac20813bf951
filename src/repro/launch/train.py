"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 50 --batch 8 --seq 128

Full substrate path: data pipeline → pjit train step (remat/ZeRO/compression
per flags) → async checkpointing → straggler monitor → restart-on-failure.
On this CPU container use --smoke (reduced config); the same flags drive the
production mesh on a real fleet.

SOL-pipeline path (``--sol``): the train step's forward AND backward ride
elected kernels —

    PYTHONPATH=src python -m repro.launch.train --smoke --sol

extracts a model-zoo block through ``optimize(training=True)``, warm-
autotunes every forward and backward impl of the graph's nodes, re-elects
from the measured cache, then HARD-ASSERTS that (a) the heavy families
elected non-reference backward kernels and (b) strict measured-provenance
holds for forward and backward elections alike, before running the training
loop.  CI runs exactly this command as the training-pipeline gate.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import get_config, get_smoke
from ..data import DataConfig, DataLoader, SyntheticTokenDataset
from ..distributed import sharding as S
from ..distributed.steps import (StepOptions, init_train_state,
                                 make_train_step)
from ..models import backbone as B
from ..runtime import StragglerMonitor
from .compile_cache import use_compile_cache
from .mesh import make_debug_mesh, make_production_mesh


_SOL_HEAVY_KINDS = ("linear", "matmul", "attention", "rglru_scan",
                    "rwkv6_scan")


def _sol_zoo_model(name: str, d_model: int):
    from ..frontends import nn
    builders = {"transformer": lambda: nn.transformer_block(d_model=d_model),
                "griffin": lambda: nn.griffin_block(d_model=d_model),
                "rwkv6": lambda: nn.rwkv6_block(d_model=d_model)}
    if name not in builders:
        raise SystemExit(f"--sol-model must be one of {sorted(builders)}")
    return builders[name]()


def _node_vals(node, rng):
    """Synthetic operands for one graph node (float specs only — the zoo
    training graphs carry no integer operands)."""
    vals = []
    for i in node.inputs:
        x = rng.standard_normal(i.spec.shape).astype(np.float32)
        vals.append(jnp.asarray(x).astype(i.spec.dtype))
    return vals


def _warm_autotune(graph, backend, *, warmup: int = 1, iters: int = 3
                   ) -> int:
    """Sweep every unique (op, shape-bucket, dtype) node of the training
    graph — forward impls AND backward impls (recorded under the
    ``_bwd``-suffixed cache keys) — into the process autotune cache, the
    same dedup discipline ``SolServer.warm_autotune`` uses for serving."""
    from ..core import autotune as AT
    from ..core import measure as M
    from ..core.ir import SOURCE_OPS, OpKind

    cache = AT.get_cache()
    rng = np.random.default_rng(0)
    seen = set()
    swept = 0
    for n in graph.topo():
        if n.op in SOURCE_OPS or n.op is OpKind.OUTPUT:
            continue
        key = (n.op.value, AT.node_shape(n), n.spec.dtype)
        if key in seen:
            continue
        seen.add(key)
        vals = _node_vals(n, rng)
        M.sweep_node(n, vals, backend, cache, warmup=warmup, iters=iters)
        M.sweep_node_grad(n, vals, backend, cache, warmup=warmup,
                          iters=iters)
        swept += 1
    return swept


def _sol_main(args) -> None:
    from ..distributed.steps import StepOptions, make_sol_train_step
    from ..frontends.optimize import optimize

    d_model = 64 if args.smoke else 256
    seq = min(args.seq, 128) if args.smoke else args.seq
    batch = min(args.batch, 4) if args.smoke else args.batch
    model = _sol_zoo_model(args.sol_model, d_model)
    shape = (batch, seq, d_model)
    backend = args.sol_backend or (
        "pallas_tpu" if jax.default_backend() == "tpu"
        else "pallas_interpret")

    # cold compile → warm the cache on the real nodes → re-elect measured
    sm = optimize(model, shape, backend=backend, training=True)
    swept = _warm_autotune(sm.graph, sm.backend)
    sm = optimize(model, shape, backend=backend, training=True)
    by_kind = sm.impl_report(by_kind=True)
    print(f"[train --sol] warmed {swept} node buckets; elections:")
    for kind, impls in sorted(by_kind.items()):
        print(f"  {kind:>20}: {impls}")

    # gate 1: the heavy families must elect NON-REFERENCE backward kernels
    for kind in _SOL_HEAVY_KINDS:
        bwd = by_kind.get(f"{kind}_bwd")
        if bwd is None:
            continue                      # family absent from this model
        ref_only = [name for name in bwd if name.startswith("ref.")]
        if ref_only:
            raise SystemExit(
                f"[train --sol] FAIL: {kind}_bwd elected reference "
                f"backward(s) {ref_only} — expected a registered backward "
                f"kernel after warm_autotune")

    # gate 2: strict measured provenance, forward and backward alike
    kinds = tuple(k for k in by_kind
                  if k in _SOL_HEAVY_KINDS
                  or k.removesuffix("_bwd") in _SOL_HEAVY_KINDS)
    violations = sm.check_provenance(kinds=kinds, require=("measured",))
    if violations:
        raise SystemExit("[train --sol] FAIL: provenance violations:\n  "
                         + "\n  ".join(violations))
    print(f"[train --sol] strict provenance clean over {sorted(kinds)}")

    # train: fwd+bwd through the elected graph
    opts = StepOptions(lr=args.lr, warmup=max(args.steps // 10, 1),
                       total_steps=args.steps, zero=False)
    step_fn, init_state = make_sol_train_step(sm, opts)
    state = init_state()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    y = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    jitted = jax.jit(step_fn)
    losses = []
    for step in range(args.steps):
        state, metrics = jitted(state, {"x": x, "y": y})
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train --sol] step {step:4d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e}")
    first, last = losses[0], losses[-1]
    if not last < first:
        raise SystemExit(f"[train --sol] FAIL: loss did not improve "
                         f"({first:.4f} -> {last:.4f})")
    print(f"[train --sol] done: loss {first:.4f} -> {last:.4f} (improved), "
          f"fwd+bwd on elected kernels")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--sol", action="store_true",
                    help="train through the SOL pipeline: optimize("
                         "training=True) + warm_autotune + elected "
                         "fwd/bwd kernels")
    ap.add_argument("--sol-model", default="transformer",
                    help="model-zoo block for --sol "
                         "(transformer|griffin|rwkv6)")
    ap.add_argument("--sol-backend", default=None,
                    help="default: pallas_tpu on a TPU, pallas_interpret "
                         "elsewhere")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16"])
    ap.add_argument("--no-zero", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=25)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    if args.sol:
        _sol_main(args)
        return

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_production_mesh() if args.production_mesh \
        else make_debug_mesh(1, 1)
    opts = StepOptions(remat=not args.no_remat, microbatch=args.microbatch,
                       grad_compression=args.grad_compression,
                       zero=not args.no_zero, lr=args.lr,
                       warmup=max(args.steps // 10, 1),
                       total_steps=args.steps)

    print(f"[train] {cfg.name}: {B.count_params(cfg):,} params, "
          f"mesh {dict(mesh.shape)}")
    step_fn, state_specs = make_train_step(mesh, cfg, opts)
    state = init_train_state(cfg, opts, jax.random.PRNGKey(0))

    dcfg = DataConfig(seed=0, vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    dataset = SyntheticTokenDataset(dcfg)
    ckpt = CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval)
    monitor = StragglerMonitor(n_hosts=1)

    # resume if a checkpoint exists; a checkpoint that fails to restore
    # is an error, not a fresh start
    start = 0
    restored_step, restored = ckpt.restore_latest(
        jax.eval_shape(lambda: state))
    if restored is not None:
        state, start = restored, restored_step
        print(f"[train] resumed from step {start}")

    loader = DataLoader(dataset, start_step=start)
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    with mesh:
        t0 = time.time()
        losses = []
        for step in range(start, args.steps):
            batch = next(loader)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = jitted(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            monitor.record_step({0: time.time() - t0})
            t0 = time.time()
            ckpt.maybe_save(step + 1, state)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
    ckpt.wait()
    loader.close()
    first = np.mean(losses[:5]) if len(losses) >= 5 else losses[0]
    last = np.mean(losses[-5:])
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
