"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --fault-seeds 4,5,6 --seconds 30

One process sets the cell up once.  For each of ``--seeds`` it draws that
seed's weights and traffic, serves a window of ``--seconds`` at the cell's
own load, and compares a sample of what it served with the plain reference
(the program's readings) and, at the same positions, the bfloat16 reference
put in the program's place (the control's readings).  For each of
``--fault-seeds`` it does the same with a fault planted under the timed
path: decode's new KV rows are never appended to a request's slot, so every
later token attends a stale cache.  Each reading goes through the check
that decides ``correct`` (``harness.check.verdict`` and ``passed``) at the
cell's limits (``bench/limits/<cell>.json``), so each line says whether
the program, the control and the fault pass.  One JSON line per seed, then
a summary line.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def stale_kv_fault():
    """Plant the fault; returns the function that takes it away."""
    from repro.launch import serve
    real = serve.SlotArena.write_kv_rows

    def keep_prompt_rows(self, slot, tensor, start_row, rows):
        if start_row == 0:
            real(self, slot, tensor, start_row, rows)
    serve.SlotArena.write_kv_rows = keep_prompt_rows
    return lambda: setattr(serve.SlotArena, "write_kv_rows", real)


def reseed(cell, seed: int) -> None:
    """New weights and embedding for another seed, on the same server and
    compiled programs.  The bucket programs' staged copies of the old
    weights are dropped first, so that two sets never share the device."""
    from harness import model as model_mod
    for m in cell.server._models.values():
        m._ctx_params = None
    model_mod.load_seeded(cell.model, cell.arch.weights(cell.lm), seed)
    cell.server.embed = cell.embedding(seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax
    from harness import check
    from harness.cell import Cell, log
    from harness.spans import Recorder
    from harness.spec import Spec
    from run import use_bench_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"control: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 3
    use_bench_compile_cache(BENCH)
    spec = Spec(BENCH.parent)
    cell = Cell(spec, args.workload)
    limits = spec.limits(args.workload)
    ref = spec.reference(cell.lm["reference"])
    seeds, fault_seeds = _seeds(args.seeds), _seeds(args.fault_seeds)
    cell.setup((seeds + fault_seeds)[0], devs[0].device_kind)

    def one(seed: int, fault: bool) -> dict:
        t0 = time.perf_counter()
        reseed(cell, seed)
        rec = Recorder()
        rec.install(cell.server)
        undo = stale_kv_fault() if fault else None
        try:
            w = cell.serve(seed, args.seconds, rec)
            # the requests done when the window closed, as a run samples
            # them; those the drain below finishes hold no logits rows
            done = cell.finished(w)
            while cell.server.depth:              # drain before the next
                cell.server.step()
        finally:
            if undo:
                undo()
            rec.uninstall()
        sample = check.sample(done, int(cell.mix["check"]["requests"]), seed)
        params = cell.params(seed)
        got = check.compare(ref, cell.lm, params, cell.embedding(seed),
                            sample, control=not fault)
        del params
        got["passed"] = check.passed(check.verdict(got, w.compiles, limits))
        if not fault:
            # the control's tokens are the ones bfloat16 puts first
            ctrl = dict(got, logit_rel_mse=got["control_logit_rel_mse"],
                        greedy_mismatches=0)
            got["control_passed"] = check.passed(
                check.verdict(ctrl, w.compiles, limits))
        got.update(seed=seed, fault="stale_kv" if fault else None,
                   compiles=w.compiles, seconds=time.perf_counter() - t0)
        print(json.dumps(got), flush=True)
        return got

    rows = [one(s, False) for s in seeds]
    faults = [one(s, True) for s in fault_seeds]
    log(f"[control] {len(rows)} seeds, {len(faults)} with the fault")
    summary = {
        "workload": args.workload, "limits": limits,
        "program_logit_rel_mse_max": max(r["logit_rel_mse"] for r in rows),
        "program_passed_all": all(r["passed"] for r in rows),
        "control_logit_rel_mse_min": min(r["control_logit_rel_mse"]
                                         for r in rows),
        "control_failed_all": not any(r["control_passed"] for r in rows),
        "program_gap_max": max(r["gap_max"] for r in rows),
        "control_gap_min": min(r["control_gap_max"] for r in rows),
        "tokens_min": min(r["tokens"] for r in rows + faults)}
    if faults:
        summary.update(
            fault_logit_rel_mse_min=min(r["logit_rel_mse"] for r in faults),
            fault_failed_all=not any(r["passed"] for r in faults))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
