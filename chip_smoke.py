"""Chip smoke: serve a 28-layer, 1536-wide LM through SOL on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # TP=4 mesh server vs one chip

The served model is ``repro.launch.serve.build_lm`` — the repo's own
LayerNorm/GELU/MHA stack — at qwen2-1.5b's published widths
(``repro/configs/qwen2_1_5b.py``: 28 layers, d_model 1536, 12 heads of 128,
vocab 151,936; MLP 4·d_model), float32, with random weights made in this
process from fixed seeds: about 1.0 B parameters, about 4 GB on the chip.
``SolServer`` serves it as ``repro.launch.serve.main`` builds it, on the
``pallas_tpu`` backend under ``strict_provenance``:

1. ``warm_autotune`` compiles and times every admissible impl of every
   LINEAR/MATMUL/ATTENTION/DECODE_ATTENTION node of every prefill and decode
   bucket the workload can open; a Pallas candidate that fails to compile
   fails the run;
2. a few greedy requests are served — one prefill bucket and decode steps
   at batch > 1 — and every served election must be ``measured``;
3. the first-token logits of two requests are compared with a plain
   float32 ``jax.numpy`` forward of the same weights at highest precision;
4. three training steps of ``make_sol_train_step`` on one block at
   (2, 512, 1536) must give a finite loss.

``--four-chips`` runs only the same server on a ``mesh=(1, 4)`` (tensor
parallel over four chips) and the one-chip server it is compared with.
Everything runs in this one process.  Any failed phase exits non-zero;
only a run that passes prints, as its last line, the JSON object
``{"ok": true, "device": {...}}``.  Without a TPU it exits 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_REQUESTS = 6
GEN_TOKENS = 16
SEED = 0
# Served and reference logits are compared relative to the reference's
# largest |logit|.  The served path runs f32 operands through XLA's and
# Mosaic's default TPU matmul precision, a single bf16 pass: each operand
# keeps 8 significant bits (relative rounding error 2^-9 ≈ 0.2%), so each
# of the ~170 chained matmuls (6 per layer × 28, plus the head) carries
# ~0.2–0.5% relative error.  These errors are independent and mostly add in
# quadrature through the residual stream; 5% of the logit scale bounds them
# with margin, while a wrong kernel (mask, head mapping, cache row) moves
# logits by O(100%).
LOGIT_RTOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase of the smoke run failed."""


def serve_config(backend: str = "pallas_tpu"):
    from repro.configs import get_config
    from repro.launch.serve import ServeConfig
    arch = get_config("qwen2-1.5b")
    return ServeConfig(d_model=arch.d_model, n_heads=arch.n_heads,
                       n_layers=arch.n_layers, vocab=arch.vocab,
                       max_seq=512, max_batch=4, slots=4, backend=backend,
                       seed=SEED)


def workload(cfg, n: int = N_REQUESTS, seed: int = SEED):
    """``n`` prompts of 3 to 24 tokens, from ``seed``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(rng.integers(3, 25)),
                         dtype=np.int32) for _ in range(n)]


def serve(cfg, model, prompts, gen: int = GEN_TOKENS, tag: str = "serve"):
    """Warm, then serve ``prompts`` greedily through one ``SolServer``.
    Returns the server, its requests and each request's first-token
    logits."""
    from repro.launch.serve import SolServer
    server = SolServer(cfg, model=model, strict_provenance=True)
    reqs = [server.submit(p, gen) for p in prompts]
    t0 = time.perf_counter()
    counts = server.warm_autotune()
    print(f"[{tag}] warm_autotune: {counts['impls']} impl timings over "
          f"{counts['nodes']} (op, shape) keys in "
          f"{time.perf_counter() - t0:.1f}s (compiles included)")
    t0 = time.perf_counter()
    first = {}
    for _ in range(10_000):
        if not server.depth:
            break
        server.step()
        for r in reqs:
            if r.rid not in first and r.last_logits is not None:
                first[r.rid] = r.last_logits.copy()
    print(f"[{tag}] served in {time.perf_counter() - t0:.1f}s "
          f"(bucket compiles included)")
    unfinished = [r.rid for r in reqs
                  if not r.done or len(r.generated) != gen]
    if unfinished:
        raise SmokeFailure(f"requests {unfinished} did not finish")
    return server, reqs, first


def check_elections(server, backend_name: str) -> None:
    """Print every served election and fail unless each one was elected
    from measurements on ``backend_name`` and every Pallas candidate of
    every served node was timed in its exact bucket."""
    from repro.backends import registry as R
    from repro.core import autotune as AT
    from repro.launch.serve import SERVED_KINDS

    bk = server.backend
    if bk.name != backend_name:
        raise SmokeFailure(f"served on {bk.name}, expected {backend_name}")
    print(f"[elections] backend={bk.name} interpret={bk.interpret} "
          f"hw={bk.hw.name}")
    bad = []
    for bucket, rec in sorted(server.served_elections.items()):
        for kind, impls in sorted(rec["by_op"].items()):
            for name, count in sorted(impls.items()):
                entry = rec["provenance"].get(name, {})
                srcs = entry.get("sources", {})
                pins = entry.get("pinned", "")
                print(f"[elections] bucket {bucket} {kind} -> {name} x{count}"
                      f" sources={srcs}" + (f" pinned={pins}" if pins else ""))
                if not srcs or set(srcs) != {"measured"}:
                    bad.append(f"{bucket}:{kind}->{name}:{srcs}")
    cache = AT.get_cache()
    timed = untimed = 0
    for m in server._models.values():
        for node in m.graph.topo():
            if node.op not in SERVED_KINDS:
                continue
            got, where = cache.lookup_with_confidence(
                node.op.value, AT.node_shape(node), node.spec.dtype,
                bk.cache_name)
            for impl in R.candidates(bk, node):
                if not impl.name.startswith("pallas."):
                    continue
                if where == "exact" and impl.name in got:
                    timed += 1
                else:
                    untimed += 1
                    bad.append(f"{node.op.value}@{AT.node_shape(node)}: "
                               f"{impl.name} not timed")
    print(f"[elections] pallas candidates timed in their exact bucket: "
          f"{timed} node checks, {untimed} missing")
    if bad:
        raise SmokeFailure("elections not measured: " + "; ".join(bad[:8]))


def reference_logits(model, embed, prompt):
    """Last-position logits of ``build_lm`` on one prompt: a plain float32
    ``jax.numpy`` forward of the model's own weights, at highest matmul
    precision — independent of the SOL graph, its elections and kernels."""
    import jax
    import jax.numpy as jnp

    sd = model.state_dict()
    blocks = model.mods[:-1]
    n_heads = blocks[0].mods[0].mods[1].n_heads

    def ln(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    def attention(x, p):
        s, d = x.shape
        hd = d // n_heads
        q, k, v = ((x @ p[w]).reshape(s, n_heads, hd)
                   for w in ("wq", "wk", "wv"))
        logits = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, d)
        return o @ p["wo"]

    def forward(sd, x):
        for i in range(len(blocks)):
            a = f"{i}.0."
            x = x + attention(ln(x, sd[a + "0.weight"], sd[a + "0.bias"]),
                              {w: sd[a + "1." + w]
                               for w in ("wq", "wk", "wv", "wo")})
            m = f"{i}.1."
            h = ln(x, sd[m + "0.weight"], sd[m + "0.bias"])
            h = jax.nn.gelu(h @ sd[m + "1.weight"].T + sd[m + "1.bias"])
            x = x + h @ sd[m + "3.weight"].T + sd[m + "3.bias"]
        head = f"{len(blocks)}."
        return x[-1] @ sd[head + "weight"].T + sd[head + "bias"]

    with jax.default_matmul_precision("highest"):
        out = jax.jit(forward)(sd, jnp.asarray(embed[prompt]))
    return np.asarray(out, np.float64)


def compare_logits(served, ref, tag: str) -> float:
    err = float(np.max(np.abs(np.asarray(served, np.float64) - ref)))
    scale = float(np.max(np.abs(ref)))
    print(f"[{tag}] max|served - reference| = {err!r} over max|reference| "
          f"= {scale!r} (ratio {err / scale!r}, tolerance {LOGIT_RTOL})")
    if not np.isfinite(err) or err > LOGIT_RTOL * scale:
        raise SmokeFailure(f"{tag}: logits differ by {err} > "
                           f"{LOGIT_RTOL} x {scale}")
    return err / scale


def print_summary(server, tag: str) -> None:
    s = server.summary()
    print(f"[{tag}] summary: requests={s['requests']} tokens={s['tokens']} "
          f"forwards={s['forwards']} dmas={s['dmas']} "
          f"prefills={s['prefills']} decodes={s['decodes']} "
          f"buckets={s['buckets']}")
    multi = [k for k in s["buckets"]
             if k.startswith("d") and int(k[1:].split("x")[0]) > 1]
    if not multi:
        raise SmokeFailure(f"{tag}: no decode step ran at batch > 1")


def one_chip(backend: str = "pallas_tpu", cfg=None) -> None:
    """The one-chip phases: serve, audit elections, compare logits, train."""
    from repro.launch.serve import build_lm
    cfg = cfg or serve_config(backend)
    model = build_lm(cfg)
    n_params = sum(int(np.prod(v.shape))
                   for v in model.state_dict().values())
    print(f"[model] build_lm: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, vocab {cfg.vocab}: {n_params:,} params "
          f"(float32)")
    prompts = workload(cfg)
    print(f"[model] {len(prompts)} greedy requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {GEN_TOKENS} new tokens each")
    server, reqs, first = serve(cfg, model, prompts)
    check_elections(server, backend)
    print_summary(server, "serve")
    for r in reqs[:2]:
        compare_logits(first[r.rid],
                       reference_logits(model, server.embed, r.prompt),
                       f"logits rid={r.rid}")
    server.close()
    train_steps(backend, cfg.d_model, cfg.n_heads, 512)


def train_steps(backend: str, d: int, heads: int, seq: int,
                steps: int = 3) -> None:
    """Three steps of ``make_sol_train_step`` on one block; finite loss."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.steps import StepOptions, make_sol_train_step
    from repro.frontends import nn
    from repro.frontends.optimize import optimize

    shape = (2, seq, d)
    t0 = time.perf_counter()
    sm = optimize(nn.transformer_block(d, heads), shape, backend=backend,
                  training=True)
    step_fn, init_state = make_sol_train_step(
        sm, StepOptions(warmup=1, total_steps=steps, zero=False))
    state = init_state()
    rng = np.random.default_rng(SEED)
    batch = {k: jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for k in ("x", "y")}
    jitted = jax.jit(step_fn)
    losses = []
    for _ in range(steps):
        state, metrics = jitted(state, batch)
        losses.append(float(metrics["loss"]))
    print(f"[train] transformer_block({d}, {heads}) at {shape}: losses "
          f"{losses} in {time.perf_counter() - t0:.1f}s (compile included)")
    if not all(np.isfinite(losses)):
        raise SmokeFailure(f"train: non-finite loss {losses}")


def four_chips(backend: str = "pallas_tpu", cfg=None) -> None:
    """TP=4 server against the one-chip server on the same weights."""
    import jax
    from repro.launch.serve import build_lm
    cfg = cfg or serve_config(backend)
    model = build_lm(cfg)
    prompts = workload(cfg)
    tp, tp_reqs, tp_first = serve(dataclasses.replace(cfg, mesh=(1, 4)),
                                  model, prompts, tag="mesh 1x4")
    check_elections(tp, backend)
    print_summary(tp, "mesh 1x4")
    placed = _placement(tp)
    tp.close()
    del tp                  # free the sharded copies before the next server
    gc.collect()
    one, one_reqs, one_first = serve(cfg, model, prompts, tag="one chip")
    check_elections(one, backend)
    one.close()
    same = sum(a.generated == b.generated for a, b in zip(tp_reqs, one_reqs))
    print(f"[compare] greedy tokens identical for {same}/{len(prompts)} "
          f"requests")
    for a, b in zip(tp_reqs, one_reqs):
        compare_logits(tp_first[a.rid], np.asarray(one_first[b.rid],
                                                   np.float64),
                       f"mesh vs one chip rid={a.rid}")
    devices = {d.id for d in jax.devices()[:4]}
    if placed != devices:
        raise SmokeFailure(f"parameters placed on devices {sorted(placed)}, "
                           f"expected {sorted(devices)}")


def _placement(server):
    """Devices holding the mesh server's parameters; prints how many
    parameters are split across four devices."""
    devices, split = set(), 0
    for m in server._models.values():
        for arr in (m._ctx_params or {}).values():
            shards = arr.addressable_shards
            devices |= {s.device.id for s in shards}
            split += len({s.device.id for s in shards}) == 4 and \
                shards[0].data.size * 4 == arr.size
    print(f"[placement] parameters on devices {sorted(devices)}; "
          f"{split} parameter arrays split in quarters over four devices")
    if not split:
        raise SmokeFailure("no parameter is sharded over four devices")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 mesh server and the one-chip "
                         "server it is compared with")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package next to this "
              f"script: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform} "
              f"devices", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"[device] {devices[0].device_kind} x{len(devices)} "
          f"(platform {devices[0].platform})")
    print(f"[device] compile cache: {use_compile_cache()}")
    from repro.core import autotune as AT
    AT.set_cache(AT.AutotuneCache())    # warm in-process, never from a file
    t0 = time.perf_counter()
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
