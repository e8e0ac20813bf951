"""The program's spans on the device trace's clock: the clock offset from
``bench.step``, the idle time cut by intersection with the innermost span,
and the metrics that read them, on hand-made records and in a traced CPU
run."""
import json

import pytest
from benchkit import REPO, TINY_LM, tiny_mix

from harness import program, trace
from harness_run import run_cell

MS = 1_000_000          # ns
OFFSET = 123.456789     # trace seconds = host seconds + OFFSET


def _span(name, a_ms, b_ms, parent=None, **attrs):
    """A program span on the host clock, from trace-clock milliseconds."""
    return (name, a_ms * 1e-3 - OFFSET, b_ms * 1e-3 - OFFSET, parent, attrs)


def _case():
    """A 10 ms window, two steps.  Step 1 (1-5 ms) gathers, stages,
    launches its program (busy 2-3 ms), fetches, writes KV rows, samples;
    step 2 (5-9 ms) likewise, its program busy 6.5-7 ms.  The device idles
    over 3-6.5 ms, across step 1's fetch, KV writes, sampling and self time
    and step 2's gather and staging."""
    spans = [
        _span("sol.step", 1.0, 5.0),                     # 0
        _span("sol.arena.sync", 1.0, 1.2, 0),
        _span("sol.decode", 1.2, 4.0, 0),                # 2
        _span("sol.gather", 1.2, 1.5, 2),
        _span("sol.stage", 1.5, 2.0, 2),                 # 4
        _span("sol.stage.pack", 1.5, 1.8, 4),
        _span("sol.stage.put", 1.8, 2.0, 4),
        _span("sol.forward", 2.0, 2.1, 2),
        _span("sol.fetch", 2.1, 3.5, 2),
        _span("sol.kv_write", 3.5, 4.0, 2),
        _span("sol.sample", 4.0, 4.6, 0),
        _span("sol.step", 5.0, 9.0),                     # 11
        _span("sol.decode", 5.0, 8.0, 11),               # 12
        _span("sol.gather", 5.0, 6.0, 12),
        _span("sol.stage", 6.0, 6.4, 12),
        _span("sol.forward", 6.4, 6.6, 12),
        _span("sol.fetch", 6.6, 7.5, 12),
        _span("sol.kv_write", 7.5, 8.0, 12),
        _span("sol.sample", 8.0, 9.0, 11),
    ]
    records = {
        "ops": {"/device:TPU:0": [["fusion.1", 2 * MS, 1 * MS, "loop fusion"],
                                  ["fusion.2", 6.5 * MS, 0.5 * MS, "loop fusion"]]},
        "spans": [["bench.window", 0, 10 * MS],
                  ["bench.step", 1 * MS, 4 * MS],
                  ["bench.step", 5 * MS, 4 * MS]],
    }
    # the benchmark's own bench.step on the host clock, a few us of jitter
    host_steps = [("bench.step", 1e-3 - OFFSET + 2e-6, 5e-3 - OFFSET, None),
                  ("bench.step", 5e-3 - OFFSET - 2e-6, 9e-3 - OFFSET, None)]
    return records, host_steps, spans


def test_the_clock_offset_is_recovered():
    records, host_steps, _ = _case()
    offset, spread, span = program.clock_offset(
        host_steps, [s for s in records["spans"] if s[0] == "bench.step"])
    assert offset == pytest.approx(OFFSET, abs=1e-9)
    assert span == pytest.approx(4e-6, abs=1e-9)
    assert spread <= program.MAX_SPREAD_S
    # one pair pushed 3 ms apart (the host thread lost its turn between
    # the two timestamps) moves neither the offset nor the spread
    trace_steps = [["bench.step", k * 10 * MS, MS] for k in range(20)]
    host = [("bench.step", k * 1e-2 - OFFSET - (3e-3 if k == 7 else 0.0),
             None, None) for k in range(20)]
    offset, spread, span = program.clock_offset(host, trace_steps)
    assert offset == pytest.approx(OFFSET, abs=1e-9)
    assert spread == pytest.approx(0.0, abs=1e-9)
    assert span == pytest.approx(3e-3)


def test_a_gap_is_split_among_the_spans_it_straddles():
    records, host_steps, spans = _case()
    idle = program.split_idle(records, spans, OFFSET)
    # idle 0-2 ms: outside 0-1, arena sync 1-1.2, gather 1.2-1.5, stage
    # 1.5-2; idle 3-6.5 ms: fetch 3-3.5, KV writes 3.5-4, sampling 4-4.6,
    # step self time 4.6-5, gather 5-6, stage 6-6.4, dispatch 6.4-6.5;
    # idle 7-10 ms: fetch 7-7.5, KV writes 7.5-8, sampling 8-9, outside 9-10
    want = {"staging": 0.2 + 0.3 + 0.5 + 0.5 + 1.0 + 0.4 + 0.5,
            "fetch": 0.5 + 0.5, "scheduler": 0.6 + 0.4 + 1.0,
            "dispatch": 0.1, "other": 0.0, "outside": 1.0 + 1.0}
    assert idle == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    # the midpoint rule gives the whole 3-6.5 ms gap to the span open at
    # 4.75 ms, step 1's own time
    mapped = [[n, (a + OFFSET) * 1e9, (b - a) * 1e9] for n, a, b, _, _ in spans]
    gaps = dict(trace.reduce(dict(records, spans=records["spans"][:1]
                                  + mapped))["idle_gaps"])
    assert gaps["sol.step"] == pytest.approx(3.5e-3)


def test_the_parts_add_up_to_the_idle_share():
    records, host_steps, spans = _case()
    shares = program.shares(records, host_steps, spans)
    s = trace.reduce(records)
    idle_share = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    assert sum(shares.values()) == pytest.approx(idle_share)
    assert shares["staging"] == pytest.approx(34.0)
    assert shares["fetch"] == pytest.approx(10.0)
    assert shares["scheduler"] == pytest.approx(20.0)


@pytest.mark.parametrize("anchors", ["fewer", "spread"])
def test_mismatched_anchors_give_no_idle_metric(anchors):
    records, host_steps, spans = _case()
    if anchors == "fewer":
        host_steps = host_steps[:1]
    else:
        name, t0, t1, meta = host_steps[1]
        host_steps[1] = (name, t0 - 2 * program.MAX_SPREAD_S, t1, meta)
    assert program.shares(records, host_steps, spans) is None


def test_spans_outside_a_step_are_outside():
    records, host_steps, spans = _case()
    stray = [_span("sol.stage", 0.0, 1.0)]
    a = program.split_idle(records, spans, OFFSET)
    b = program.split_idle(records, spans + stray, OFFSET)
    assert a == pytest.approx(b)


def test_traced_cpu_run_reports_program_counters(checkout, fast_autotune,
                                                 capsys):
    """A traced CPU run reads the padding and compile counters; the idle
    shares need a chip's device trace and are left out."""
    base = json.loads((REPO / "bench/traffic/chat-poisson.json").read_text())
    checkout.add_cell("tiny-program", "tiny-cfg", "tiny-mix", lm=TINY_LM,
                      mix=tiny_mix(base), ttft=True)
    line = run_cell(checkout, "tiny-program", capsys, trace="1")
    got = line["metrics"]
    assert 0 < got["sched.padded_token_share"]["value"] < 100
    assert got["sched.padded_token_share"]["unit"] == "%"
    assert got["setup.compile_s"]["value"] > 0
    assert got["setup.compile_s"]["value"] <= got["setup.programs_s"]["value"]
    assert not {"stage.idle_share", "stage.d2h_idle_share",
                "sched.idle_share"} & set(got)
