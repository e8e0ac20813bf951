"""Serve batched requests THROUGH the SOL pipeline: continuous batching on
the elected/tuned graph.

Requests are admitted into a slot arena (token regions on the AsyncQueue,
KV rows resident on the device), padded to the same pow2 buckets the
autotune cache keys on (so served shapes hit measured timings and pinned
Tunable configs), staged host→device with one packed DMA per step, and
decoded by SolModels whose LINEAR/MATMUL/ATTENTION
elections all carry measured provenance.  The second leg replays the same
workload from framework-free deploy artifacts (paper Sec. III-C).

    PYTHONPATH=src python examples/serve_batch.py [--backend pallas_interpret]
"""
import argparse
import sys
sys.path.insert(0, "src")

from repro.core import autotune as AT
from repro.launch.serve import ServeConfig, SolServer, _smoke_workload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--gen", type=int, default=8)
    args = ap.parse_args()

    cfg = ServeConfig(d_model=64, n_heads=4, n_layers=2, vocab=128,
                      max_seq=64, max_batch=4, slots=6,
                      backend=args.backend)
    AT.set_cache(AT.AutotuneCache())          # private, in-memory cache
    server = SolServer(cfg, strict_provenance=True)
    workload = _smoke_workload(cfg, args.requests, args.gen)
    for prompt, gen in workload:
        server.submit(prompt, gen)

    counts = server.warm_autotune()
    print(f"warmed autotune cache: {counts['impls']} impl timings over "
          f"{counts['nodes']} (op, shape) keys")
    summary = server.run()
    print(f"{summary['requests']} requests → {summary['tokens']} tokens in "
          f"{summary['steps']} steps ({summary['tokens_per_s']:.1f} tok/s, "
          f"{summary['dmas']} packed DMAs)")
    print(f"latency p50/p99 {summary['latency_ms']['p50']:.0f}/"
          f"{summary['latency_ms']['p99']:.0f} ms, "
          f"ttft p50 {summary['ttft_ms']['p50']:.0f} ms, "
          f"buckets {summary['buckets']}")
    for bucket, rec in sorted(server.served_elections.items()):
        kinds = {k: list(v) for k, v in rec["by_op"].items()}
        print(f"  bucket {bucket}: {kinds}")

    # deployment loop: export every bucket model, serve from the artifacts
    arts = server.export_artifacts()
    replay = SolServer(cfg, deployed=arts, strict_provenance=True)
    reqs = [replay.submit(p, g) for p, g in workload]
    replay.run()
    live = {r.rid: r.generated for r in server._finished}
    same = all(r.generated == live[r.rid] for r in reqs)
    print(f"deploy round-trip over {len(arts)} artifacts: "
          f"{'bit-identical' if same else 'DIVERGED'}")
    server.close()
    replay.close()


if __name__ == "__main__":
    main()
