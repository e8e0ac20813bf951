"""The harness end to end on the CPU at tiny widths: driven by data, every
mix, the faults the check must catch, and no result without a chip."""
import json
import os
import subprocess
import sys

import pytest
from benchkit import REPO, TINY_LM, tiny_mix

from harness_run import contract_ok, run_cell
from harness import spec as spec_mod


def test_new_cell_from_new_files_only(checkout, fast_autotune, capsys):
    """A configuration, a mix and a cell added as files and entries run end
    to end in interpret mode, and the harness's files stay as they were."""
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*")
              if p.is_file() and ".cache" not in p.parts
              and "__pycache__" not in p.parts}
    base = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    mix = tiny_mix(base, max_seq=16, max_batch=1, slots=2)
    mix["prompt_len"].update(min=5, max=6)
    mix["output_len"].update(min=2, max=3)
    checkout.add_cell("tiny-interp", "tiny-cfg", "tiny-steady", lm=TINY_LM,
                      mix=mix, ttft=True)
    line = run_cell(checkout, "tiny-interp", capsys, backend="pallas_interpret")
    contract_ok(line, ["ttft_p90_ms", "itl_p95_ms", "setup_s"])
    assert line["correct"] is True
    assert line["check"]["compiles_in_window"]["value"] == 0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_first_run_tunes_in_a_process_of_its_own(checkout, capsys):
    """With no autotune cache in the checkout, the run measures it in a
    child process (``bench/tune.py``), then serves from the saved file."""
    import run as bench_run
    base = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    mix = tiny_mix(base, max_seq=16, max_batch=1, slots=2)
    mix["prompt_len"].update(min=5, max=6)
    mix["output_len"].update(min=2, max=3)
    checkout.add_cell("tiny-tune", "tiny-cfg", "tiny-tune", lm=TINY_LM,
                      mix=mix, ttft=True)
    rc = bench_run.main(["--workload", "tiny-tune", "--seed", "2147483659",
                         "--seconds", "0.5", "--trace", "0"],
                        root=checkout.root, backend="xla", require_tpu=False,
                        compile_cache=False)
    assert rc == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert cap.err.count("[setup] autotune measured") == 1
    assert list((checkout.root / "bench/.cache/autotune")
                .glob("tiny-tune__*.json"))


def test_traced_run_reports_per_layer_metrics(checkout, fast_autotune,
                                              capsys):
    base = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    checkout.add_cell("tiny-traced", "tiny-cfg", "tiny-mix", lm=TINY_LM,
                      mix=tiny_mix(base), ttft=True)
    line = run_cell(checkout, "tiny-traced", capsys, trace="1")
    assert line["correct"] is True
    got = set(line["metrics"])
    # read from spans and counters; the device shares need a chip's trace
    assert {"sched.host_ms_per_step", "sched.queue_wait_ms",
            "stage.h2d_bytes_per_token", "stage.d2h_bytes_per_token",
            "setup.programs_s"} <= got
    for name in got:
        assert line["metrics"][name]["value"] > 0
    assert "busy_s" in line["device"] and "window_s" in line["device"]


def test_no_accelerator_no_result(tmp_path):
    """On the CPU the entry point exits non-zero and prints no result."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench/run.py"), "--workload",
         "qwen2w-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_compile_cache_in_the_checkout_and_unbounded(tmp_path):
    """The benchmark's compilation cache is its own directory, without the
    bound a machine's environment may set: under a bound JAX scans every
    entry on each write, which made a first run's tuning three times as
    long on the chip."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import jax, run; "
            "d = run.use_bench_compile_cache(run.Path(sys.argv[2])); "
            "jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones(5)).block_until_ready(); "
            "print(d, jax.config.jax_compilation_cache_max_size)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "elsewhere"),
               JAX_COMPILATION_CACHE_MAX_SIZE="201326592")
    proc = subprocess.run(
        [sys.executable, "-c", code, str(REPO / "bench"),
         str(tmp_path / "bench")], capture_output=True, text=True,
        timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    cache = tmp_path / "bench" / ".cache" / "jax"
    assert proc.stdout.split() == [str(cache), "-1"]
    names = [p.name for p in cache.iterdir()]
    assert any(n.endswith("-cache") for n in names)
    assert not any(n.endswith("-atime") for n in names)     # no eviction
    assert not (tmp_path / "elsewhere").exists()


def test_same_seed_same_inputs_and_every_seed_same_sizes():
    from harness import traffic
    mix = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    a = traffic.open_loop(mix, 2**31 + 7, 20.0, 1000)
    b = traffic.open_loop(mix, 2**31 + 7, 20.0, 1000)
    c = traffic.open_loop(mix, 5, 20.0, 1000)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    # every seed sends the same requests at the same times; only the
    # token ids differ
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in c]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    assert len({len(r.prompt) for r in a}) > 1
    closed = json.loads((REPO / "bench/traffic/decode-closed.json")
                        .read_text())
    x = traffic.closed_loop(closed, 1, 1000)
    y = traffic.closed_loop(closed, 2, 1000)
    assert [[(len(r.prompt), r.max_new, r.think_s) for r in q] for q in x] \
        == [[(len(r.prompt), r.max_new, r.think_s) for r in q] for q in y]
    assert x[0][0].prompt.tolist() != y[0][0].prompt.tolist()


def test_unknown_cell_is_refused():
    spec = spec_mod.Spec(REPO)
    with pytest.raises(spec_mod.SpecError):
        spec.cell("no-such-cell")
