"""Faults planted under the timed path turn ``correct`` false."""
import json

from benchkit import REPO, TINY_LM, tiny_mix

from harness_run import run_cell


def _fault_cell(checkout, fast_autotune):
    """A tiny cell held to the limits of the committed cell."""
    base = json.loads((REPO / "bench/traffic/decode-closed.json").read_text())
    limits = json.loads((REPO / "bench/limits/qwen2w-chat.json").read_text())
    checkout.add_cell("tiny-fault", "tiny-cfg", "tiny-fault", lm=TINY_LM,
                      mix=tiny_mix(base), limits=dict(limits, tokens_min=4))
    return "tiny-fault"


def test_altered_token_fails_the_check(checkout, fast_autotune, capsys,
                                       monkeypatch):
    """A token altered where it is produced: the sampler's pick moved to
    the next vocabulary row."""
    from repro.launch import serve
    cell = _fault_cell(checkout, fast_autotune)
    real = serve.sample_token
    monkeypatch.setattr(serve, "sample_token",
                        lambda *a, **k: (real(*a, **k) + 1) % TINY_LM["vocab"])
    line = run_cell(checkout, cell, capsys)
    assert line["correct"] is False
    assert line["check"]["greedy_mismatches"]["value"] > 0


def test_unchanged_state_fails_the_check(checkout, fast_autotune, capsys,
                                         monkeypatch):
    """A step that leaves its state unchanged: decode's new KV rows are
    never appended to the slot, so later tokens attend a stale cache."""
    from repro.launch import serve
    cell = _fault_cell(checkout, fast_autotune)
    real = serve.SlotArena.write_kv_rows

    def keep_prompt_rows(self, slot, tensor, start_row, rows):
        if start_row == 0:
            real(self, slot, tensor, start_row, rows)
    monkeypatch.setattr(serve.SlotArena, "write_kv_rows", keep_prompt_rows)
    line = run_cell(checkout, cell, capsys)
    assert line["correct"] is False


