"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
harness (``bench/harness``) finds their files by name.  With ``--trace 0``
the result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from the profiler trace of the window and from the
benchmark's spans.  Every run checks what the window served against the
plain reference and prints each number compared beside its limit.  Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def use_bench_compile_cache(bench: Path = BENCH) -> str:
    """JAX's persistent compilation cache in the benchmark's own fixed
    directory in the checkout, whatever directory the machine's environment
    names, so that two checkouts never share compiled programs.  The
    program's ``use_compile_cache`` takes it from the environment.  The
    cache is unbounded, whatever size the environment sets: under a bound
    JAX reads every entry's size and access time on each write and locks a
    file on each read, and a first run writes some 800 programs."""
    cache_dir = str(bench / ".cache" / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax
    from repro.launch.compile_cache import use_compile_cache
    jax.config.update("jax_compilation_cache_dir", use_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def tune_apart(root: Path, args, backend: str, require_tpu: bool,
               compile_cache: bool) -> None:
    """A checkout's first run measures the cell's autotune cache in a
    process of its own (``bench/tune.py``), before this one touches the
    chip, and waits for it; this process then loads the saved cache as
    every later run does."""
    from harness.cell import log
    cmd = [sys.executable, str(BENCH / "tune.py"), "--workload",
           args.workload, "--seed", str(args.seed), "--root", str(root),
           "--backend", backend]
    cmd += [] if require_tpu else ["--cpu"]
    cmd += [] if compile_cache else ["--no-compile-cache"]
    proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    for line in proc.stdout.splitlines():
        log(line)
    if proc.returncode not in (0, 3):       # 3: no chip, found again below
        raise RuntimeError(f"bench/tune.py exited with {proc.returncode}")


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, AttributeError):
            pass
    return max(peaks) if peaks else 0


def run(args, *, root: Path = BENCH.parent, backend: str = "pallas_tpu",
        require_tpu: bool = True, compile_cache: bool = True,
        tune_first: bool = True) -> dict:
    from harness import check, peaks as peaks_mod, trace as trace_mod
    from harness.cell import Cell, log
    from harness.measure import due_in, end_to_end
    from harness.readers import RunData
    from harness.spans import Recorder
    from harness.spec import Spec

    spec = Spec(root)
    cell = Cell(spec, args.workload, backend=backend)
    limits = spec.limits(args.workload)
    # tune_first=False measures in this process: the benchmark's CPU tests
    # put a fake measurement there
    if tune_first and not cell.tuned():
        tune_apart(root, args, backend, require_tpu, compile_cache)
    devs = device_info(int(cell.cell["chips"]), require_tpu)
    kind = devs[0].device_kind
    dev_line = {"platform": devs[0].platform, "kind": kind,
                "count": len(devs)}
    log(f"[device] platform={dev_line['platform']} device_kind={kind} "
        f"count={len(devs)}")
    pk = peaks_mod.peaks(kind) if require_tpu else None

    if compile_cache:
        log(f"[setup] compile cache {use_bench_compile_cache(spec.bench)}")
    phases = cell.setup(args.seed, kind)
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f}s: weights and server {phases['weights_s']:.3f}s,"
        f" bucket programs {phases['programs_s']:.3f}s "
        f"({len(cell.bucket_plan())} (batch, seq) buckets)")

    trace_dir = None
    if args.trace:
        trace_dir = spec.bench / ".cache" / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = Recorder(sync=bool(args.trace))
    rec.install(cell.server)
    try:
        w = cell.serve(args.seed, args.seconds, rec, trace_dir)
    finally:
        rec.uninstall()
    mem = memory_peak(devs)
    log(f"[lead-in] {w.lead_in_s:.3f}s, {w.lead_in_tokens} tokens "
        f"(not in setup_s)")
    log(f"[window] {w.t1 - w.t0:.3f}s, {len(w.steps)} steps, "
        f"{len(w.records)} requests seen, generator late by at most "
        f"{w.late_s * 1e3:.1f} ms")
    log(f"[window] compilations inside the window: {w.compiles}")

    out = {"correct": False, "attempted": len(due_in(w)), "failed": 0,
           "metrics": {}, "device": dict(dev_line, memory_peak_bytes=mem)}
    if args.trace:
        records = trace_mod.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = trace_mod.reduce(records)
        data = RunData(cell=cell, window=w, spans=rec.spans, setup=phases,
                       records=records, summary=summary, peaks=pk)
        for m in spec.per_layer(args.workload):
            v = spec.reader(m["name"])(data)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"].update(busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    else:
        e2e = end_to_end(w, setup_s)
        for m in spec.end_to_end(args.workload):
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}

    # the check: reference over a sample of finished requests, after the
    # program's state is freed
    mix_check = cell.mix.get("check", {})
    sample = check.sample(cell.finished(w), int(mix_check.get("requests", 4)),
                          args.seed)
    lm = cell.lm
    cell.free()
    del rec
    ref = spec.reference(lm["reference"])
    t0 = time.perf_counter()
    params = cell.params(args.seed)
    embed = cell.embedding(args.seed)
    readings = check.compare(ref, lm, params, embed, sample)
    del params
    checks = check.verdict(readings, w.compiles, limits)
    out["correct"] = check.passed(checks)
    log(f"[check] {len(sample)} requests, {readings['tokens']} served tokens "
        f"against the reference in {time.perf_counter() - t0:.3f}s; widest "
        f"gap of a served token below the reference's best "
        f"{readings['gap_max']!r} (reported, not compared)")
    for name, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        log(f"[check] {name} = {c['value']!r} (limit {rel} {c['limit']!r})")
    out["check"] = checks
    return out


def main(argv=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args, **kw)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except ImportError as e:
        print(f"bench: cannot import the system under test: {e}",
              file=sys.stderr)
        return 4
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
