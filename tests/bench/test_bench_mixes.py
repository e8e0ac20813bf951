"""Each traffic mix runs end to end at tiny widths on the CPU."""
import json

import pytest
from benchkit import REPO, TINY_LM, tiny_mix

from harness_run import run_cell


@pytest.mark.parametrize("mix_name", ["chat-poisson", "decode-closed",
                                      "longctx-closed"])
def test_each_mix_runs_at_tiny_widths(checkout, fast_autotune, capsys,
                                      mix_name):
    base = json.loads((REPO / f"bench/traffic/{mix_name}.json").read_text())
    checkout.add_cell("tiny-" + mix_name, "tiny-cfg", "tiny-" + mix_name,
                      lm=TINY_LM, mix=tiny_mix(base),
                      ttft=base["loop"] == "open")
    line = run_cell(checkout, "tiny-" + mix_name, capsys)
    assert line["correct"] is True
    assert line["metrics"]["itl_p95_ms"]["value"] > 0
    assert line["check"]["tokens_checked"]["value"] >= 4


