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


# -- the scope of each device operation ---------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num: int, v) -> bytes:
    """One protobuf field: a varint, or a length-delimited string/message."""
    if isinstance(v, int):
        return _varint(num << 3) + _varint(v)
    v = v.encode() if isinstance(v, str) else v
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _stat(meta: int, text=None, ref=None) -> bytes:
    return _f(1, meta) + (_f(5, text) if text is not None else _f(7, ref))


def _event(meta: int, off_ps: int, dur_ps: int, *stats) -> bytes:
    return (_f(1, meta) + _f(2, off_ps) + _f(3, dur_ps)
            + b"".join(_f(4, s) for s in stats))


def _plane(pid, name, lines, event_meta, stat_meta) -> bytes:
    return (_f(1, pid) + _f(2, name)
            + b"".join(_f(3, _f(2, ln) + _f(3, ts)
                          + b"".join(_f(4, e) for e in evs))
                       for ln, ts, evs in lines)
            + b"".join(_f(4, _f(1, k) + _f(2, _f(1, k) + v))
                       for k, v in event_meta.items())
            + b"".join(_f(5, _f(1, k) + _f(2, _f(1, k) + _f(2, v)))
                       for k, v in stat_meta.items()))


def _xspace() -> bytes:
    """A host plane with the window span, and a device plane whose ``XLA
    Ops`` events carry their scope as the metadata's ``tf_op`` stat (as a
    string, or as a reference to a stat metadata entry that holds it) or
    not at all; an event's own stats do not name its scope."""
    stat_meta = {1: "tf_op", 2: "jit(f)/linear:pallas.mm/dot_general"}
    event_meta = {
        10: _f(2, "%fusion.1 = f32[4]{0} fusion()")
        + _f(5, _stat(1, text="jit(f)/matmul:ref/dot_general")),
        11: _f(2, "%copy.2") + _f(5, _stat(1, ref=2)),
        12: _f(2, "%custom-call.3")}
    ops = [_event(10, 0, 10**9), _event(11, 2 * 10**9, 5 * 10**8),
           _event(12, 3 * 10**9, 7 * 10**9),
           _event(11, 9 * 10**9, 10**6, _stat(1, text="jit(g)/gelu:x"))]
    device = _plane(3, "/device:TPU:0",
                    [("XLA Modules", 1000, [_event(12, 0, 5000)]),
                     ("XLA Ops", 1000, ops)], event_meta, stat_meta)
    host = _plane(1, "/host:CPU", [("python", 0, [_event(5, 0, 10**10)])],
                  {5: _f(2, "bench.window")}, {})
    return _f(1, host) + _f(1, device)


def test_load_keeps_each_operation_s_scope(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace())
    rec = trace.load(str(tmp_path))
    assert rec["spans"] == [["bench.window", 0.0, 10.0 * MS]]
    assert rec["ops"]["/device:TPU:0"] == [
        ["%fusion.1 = f32[4]{0} fusion()", 1000.0, 1.0 * MS,
         "jit(f)/matmul:ref/dot_general"],
        ["%copy.2", 1000.0 + 2 * MS, 0.5 * MS,
         "jit(f)/linear:pallas.mm/dot_general"],
        ["%custom-call.3", 1000.0 + 3 * MS, 7.0 * MS, ""],
        ["%copy.2", 1000.0 + 9 * MS, 1000.0,
         "jit(f)/linear:pallas.mm/dot_general"]]


def test_scope_seconds_by_node_kind_any_implementation():
    rec = {"spans": [["bench.window", 0, 10 * MS]], "ops": {
        "/device:TPU:0": [
            ["a", 1 * MS, 2 * MS, "jit(run)/jit(fn)/linear:pallas.mm/dot"],
            ["b", 3 * MS, 1 * MS, "jit(run)/jit(fn)/linear:ref.linear"],
            ["c", 4 * MS, 1 * MS, "jit(run)/jit(fn)/matmul:ref.matmul/x"],
            ["d", 5 * MS, 1 * MS, "jit(run)/decode_attention:ref/x"],
            ["e", 6 * MS, 1 * MS, ""],
            ["f", 11 * MS, 1 * MS, "jit(run)/linear:pallas.mm"]]}}
    assert trace.scope_seconds(rec, ["linear"]) == pytest.approx(0.003)
    assert trace.scope_seconds(rec, ["linear", "matmul"]) == \
        pytest.approx(0.004)
    assert trace.scope_seconds(rec, ["attention"]) == 0.0
    assert trace.scope_seconds(rec, ["decode_attention"]) == \
        pytest.approx(0.001)


def test_prefetch_seconds_by_weight_shape():
    """Unscoped asynchronous copies of a weight matrix or of a block of its
    rows count; a copy of another shape, another op, a scoped copy or one
    after the window does not."""
    rec = {"spans": [["bench.window", 0, 10 * MS]], "ops": {
        "/device:TPU:0": [
            ["%async-done.1 = f32[384,8960]{1,0:T(8,128)} async-done("
             "%async-start.1)", 1 * MS, 2 * MS, ""],
            ["%copy-start.2 = (f32[1536,256]{1,0}, f32[1536,256]{1,0}, "
             "u32[]) copy-start(f32[1536,256]{1,0} %p)", 3 * MS, MS, ""],
            ["async-done f32[384,1536]", 4 * MS, MS, ""],
            ["%copy-done.3 = f32[1,512,2,128]{3,2,1,0} copy-done(%c)",
             5 * MS, MS, ""],
            ["%fusion.4 = f32[384,8960]{1,0} fusion(%x)", 6 * MS, MS, ""],
            ["async-done f32[384,8960]", 7 * MS, MS,
             "jit(run)/linear:ref.linear"],
            ["async-done f32[384,8960]", 11 * MS, MS, ""]]}}
    mats = {(1536, 8960), (1536, 256)}
    assert trace.prefetch_seconds(rec, mats) == pytest.approx(0.003)
    assert trace.prefetch_seconds(rec, mats | {(8960, 1536)}) == \
        pytest.approx(0.004)
    assert trace.prefetch_seconds(rec, set()) == 0.0
