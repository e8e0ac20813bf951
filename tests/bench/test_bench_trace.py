"""The reduction from trace records to busy time, idle gaps and the
longest device operations: on hand-made records, and on a slice of a trace
recorded on a v5e."""
import numpy as np
import pytest
from benchkit import REPO

from harness import trace

MS = 1_000_000          # ns


def _records():
    """A 10 ms window; two steps, each a stage span then a forward span;
    device ops at 1-3 ms (overlapping pair), 4-5 ms, 7-8 ms, and one op
    that starts before the window."""
    return {
        "ops": {"/device:TPU:0": [
            ["fusion.1", -1 * MS, 2 * MS, "loop fusion"],
            ["custom-call.7", 1 * MS, 2 * MS, "custom-call"],
            ["fusion.12", 2 * MS, 1 * MS, "loop fusion"],
            ["custom-call.9", 4 * MS, 1 * MS, "custom-call"],
            ["convolution.3", 7 * MS, 1 * MS, "convolution"],
        ]},
        "spans": [
            ["bench.window", 0, 10 * MS],
            ["bench.step", 0, 5 * MS],
            ["bench.stage", 0, 1 * MS],
            ["bench.forward", 1 * MS, 4 * MS],
            ["bench.step", 6 * MS, 3 * MS],
            ["bench.forward", 6 * MS, 2 * MS],
        ],
    }


def test_busy_is_the_union_inside_the_window():
    s = trace.reduce(_records())
    assert s["window_s"] == pytest.approx(0.010)
    # [0,1) of the early op, [1,3), [4,5), [7,8): 5 ms
    assert s["busy_s"] == pytest.approx(0.005)
    assert s["devices"] == 1
    assert s["longest_gap_s"] == pytest.approx(0.002)


def test_idle_gaps_by_the_host_span_they_fell_in():
    gaps = dict(trace.reduce(_records())["idle_gaps"])
    # [3,4) in step 1's forward; [5,7) centred in step 2's forward;
    # [8,10) centred after step 2
    assert gaps == pytest.approx({"bench.step/bench.forward": 0.003,
                                  "no span": 0.002})


def test_device_ops_by_name_and_span():
    ops = dict(trace.reduce(_records())["device_ops"])
    assert ops["custom-call @ bench.step/bench.forward"] == \
        pytest.approx(0.003)
    assert ops["convolution @ bench.step/bench.forward"] == \
        pytest.approx(0.001)
    assert "fusion @ no span" not in ops       # started before the window


def test_base_name():
    assert trace.base_name("fusion.123") == "fusion"
    assert trace.base_name("copy") == "copy"
    assert trace.base_name("a.b") == "a.b"


def test_a_trace_without_window_is_refused():
    r = _records()
    r["spans"] = r["spans"][1:]
    with pytest.raises(ValueError):
        trace.reduce(r)


def test_recorded_v5e_trace_slice():
    """0.6 s of a traced `qwen2w-chat` window on one v5e (device ops and the
    benchmark's spans, the window span cut to the slice): busy and idle time
    add up to the window, busy time is the union of the operations, and the
    longest operation is the decode staging's unpack."""
    rec = trace.read(str(REPO / "bench/fixtures/trace_v5e_qwen2w_chat.json.gz"))
    s = trace.reduce(rec)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(0.6)
    w0, w1 = trace.window_of(rec)
    grid = np.zeros(int((w1 - w0) / 1000) + 1, bool)        # 1 us cells
    for _, start, dur, _ in rec["ops"]["/device:TPU:0"]:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            grid[int((a - w0) / 1000):int(np.ceil((b - w0) / 1000))] = True
    assert s["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    idle = sum(v for _, v in trace.reduce(rec, top=100)["idle_gaps"])
    assert s["busy_s"] + idle == pytest.approx(s["window_s"], rel=1e-9)
    name, _ = s["device_ops"][0]
    assert name.startswith("reshape u8[29366304,4] @ ")
