"""A configuration that brings its own architecture as new files alone: a
model module, a plain reference and a configuration, added to a checkout
whose ``bench/harness/`` stays as it is, serve and pass the check on the
CPU.  The architecture (``tests/bench/arch/local_global_lm.py``) has three
layer kinds (a leading dense layer, then local and global attention in
turn), attention at a ``head_dim`` of its own and its own embedding."""
import json
import shutil

import pytest
from benchkit import REPO, tiny_mix

from harness_run import contract_ok, run_cell

ARCH = REPO / "tests/bench/arch"
LG_LM = {"d_model": 32, "n_layers": 3, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 48, "vocab": 64, "window": 4,
         "leading": ["dense"], "period": ["local", "global"],
         "dtype": "float32", "matmul_precision": "default",
         "model": "local_global_lm", "reference": "local_global_lm"}


def _harness_bytes(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench/harness").glob("*.py")}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_second_architecture_from_new_files_only(checkout, fast_autotune,
                                                 capsys, trace):
    shutil.copy(ARCH / "local_global_lm.py",
                checkout.root / "bench/models/local_global_lm.py")
    shutil.copy(ARCH / "local_global_lm_ref.py",
                checkout.root / "bench/references/local_global_lm.py")
    base = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    checkout.add_cell("tiny-lg", "tiny-lg-cfg", "tiny-lg-mix", lm=LG_LM,
                      mix=tiny_mix(base), ttft=True)
    line = run_cell(checkout, "tiny-lg", capsys, trace=trace)
    assert line["correct"] is True
    # prompts of 4 to 12 tokens and answers of 3 to 8 reach past the
    # local layer's window of 4
    assert line["check"]["tokens_checked"]["value"] >= 4
    assert line["check"]["greedy_mismatches"]["value"] == 0
    if trace == "0":
        contract_ok(line, ["ttft_p90_ms", "itl_p95_ms", "setup_s"])
    else:
        # the program's spans and counters read as for any architecture
        assert {"sched.padded_token_share", "stage.d2h_bytes_per_token",
                "stage.h2d_bytes_per_token"} <= set(line["metrics"])
    assert _harness_bytes(checkout.root) == _harness_bytes(REPO)


def test_the_check_fails_a_reference_that_drops_the_window(
        checkout, fast_autotune, capsys):
    """The comparison reaches the local layers: a reference that attends
    every earlier position there disagrees with what was served."""
    shutil.copy(ARCH / "local_global_lm.py",
                checkout.root / "bench/models/local_global_lm.py")
    ref = (ARCH / "local_global_lm_ref.py").read_text()
    assert 'if kind == "local":' in ref
    (checkout.root / "bench/references/local_global_lm.py").write_text(
        ref.replace('if kind == "local":', 'if False:'))
    base = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    checkout.add_cell("tiny-lg", "tiny-lg-cfg", "tiny-lg-mix", lm=LG_LM,
                      mix=tiny_mix(base), ttft=True)
    line = run_cell(checkout, "tiny-lg", capsys)
    assert line["correct"] is False
