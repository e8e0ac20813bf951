"""Measure one cell's autotune cache in a process of its own, and exit.

    python3 bench/tune.py --workload <cell> --seed <n>

``bench/run.py`` runs this, and waits for it, when the checkout holds no
autotune cache for the cell yet, before it touches the chip itself.  The
serving process then loads the saved cache as every later run does, so
the bucket programs it compiles are the ones later runs find in the
compile cache.  Without a TPU it exits 3.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(BENCH.parent))
    ap.add_argument("--backend", default="pallas_tpu")
    ap.add_argument("--cpu", action="store_true",
                    help="run on whatever JAX finds (the benchmark's tests)")
    ap.add_argument("--no-compile-cache", action="store_true")
    args = ap.parse_args(argv)
    from harness.cell import Cell
    from harness.spec import Spec
    from run import NoDevice, device_info, use_bench_compile_cache

    spec = Spec(Path(args.root))
    cell = Cell(spec, args.workload, backend=args.backend)
    try:
        devs = device_info(int(cell.cell["chips"]), not args.cpu)
    except NoDevice as e:
        print(f"tune: {e}", file=sys.stderr)
        return 3
    if not args.no_compile_cache:
        use_bench_compile_cache(spec.bench)
    cell.tune(args.seed, devs[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
