"""Run the harness in-process on the CPU, and read its last line."""
import json

import run as bench_run


def contract_ok(line: dict, metrics) -> None:
    """The last line as the contract reads it."""
    assert list(line)[-1] == "check"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in line["device"]
    for c in line["check"].values():
        assert "value" in c and "limit" in c


def run_cell(checkout, cell, capsys, seconds="0.6", trace="0",
             backend="xla", seed="3000000011"):
    rc = bench_run.main(["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--trace", trace], root=checkout.root,
                        backend=backend, require_tpu=False,
                        compile_cache=False, tune_first=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
