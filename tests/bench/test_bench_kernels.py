"""A kernel's share of its roofline (``kernel.matmul_roofline``) on a
hand-made trace with scopes and on a slice of a trace recorded on a v5e,
each with the program's spans, and the staging counter
``stage.d2h_bytes_per_token``."""
import dataclasses
import re

import pytest
from benchkit import REPO, TINY_LM

from harness import peaks, program, trace
from harness.readers import RunData
from harness.spec import Spec, lm_widths

SPEC = Spec(REPO)
MS = 1_000_000
LM = lm_widths({"lm": TINY_LM})
V5E = peaks.peaks("TPU v5 lite")


@dataclasses.dataclass
class _Cell:
    lm: dict
    arch: object = SPEC.model("pre_ln_gelu_lm")


def _records():
    """A 10 ms window with two steps; 2 ms of operations under ``linear:``
    scopes (one before the window), 1 ms under ``matmul:``, 0.5 ms under
    the scope of an MLP weight (its layout copy), 0.5 ms under a norm
    weight's, 1 ms under another node's and 1 ms under none, then 0.5 ms
    of an unscoped copy of a block of an MLP weight's rows and 0.25 ms of
    an unscoped copy of KV rows."""
    return {"spans": [["bench.window", 0, 10 * MS], ["bench.step", 0, 5 * MS],
                      ["bench.step", 6 * MS, 3 * MS]],
            "ops": {"/device:TPU:0": [
                ["x", -2 * MS, 1 * MS, "jit(run)/linear:pallas.mm"],
                ["a", 1 * MS, 1 * MS, "jit(run)/jit(fn)/linear:pallas.mm/d"],
                ["b", 2 * MS, 1 * MS, "jit(run)/jit(fn)/linear:ref.linear"],
                ["c", 3 * MS, 1 * MS, "jit(run)/jit(fn)/matmul:ref.matmul"],
                ["d", 4 * MS, 1 * MS, "jit(run)/jit(fn)/gelu:ref"],
                ["w", 5 * MS, MS // 2, "params['1.1.3.weight']:"],
                ["g", 6 * MS, MS // 2, "params['1.1.0.weight']:"],
                ["e", 7 * MS, 1 * MS, ""],
                ["%async-done.4 = f32[16,64]{1,0} async-done(%async-start.4)",
                 8 * MS, MS // 2, ""],
                ["%copy-done.5 = f32[1,16,2,16]{3,2,1,0} copy-done(%c)",
                 9 * MS, MS // 4, ""]]}}


def _program_spans():
    """On the host clock, 100 s behind the trace's: a decode at bucket
    2x64 and a prefill at 1x16 in the window, a decode after it."""
    return [("sol.step", 100.000, 100.005, None, {}),
            ("sol.decode", 100.001, 100.004, 0, {"bucket": "2x64"}),
            ("sol.step", 100.006, 100.009, None, {}),
            ("sol.prefill", 100.0065, 100.0085, 2, {"bucket": "1x16"}),
            ("sol.step", 100.020, 100.021, None, {}),
            ("sol.decode", 100.0201, 100.0205, 4, {"bucket": "4x64"})]


def _run(records):
    host_steps = [("bench.step", 100.000, 100.005, None),
                  ("bench.step", 100.006, 100.009, None)]
    return RunData(cell=_Cell(LM), window=None, spans=host_steps, setup={},
                   records=records, summary=None, peaks=V5E)


def _least_s(rows: int) -> float:
    """Every projection of the tiny model at ``rows`` rows, by hand: the
    MLP (32x64, 64x32) and attention (32x32, 32x16, 32x16, 32x32) of two
    layers and the head (32x64), each bound by its bytes or its FLOPs."""
    shapes = [(32, 64), (64, 32), (32, 32), (32, 16), (32, 16), (32, 32)] \
        * 2 + [(32, 64)]
    return sum(max(2 * rows * k * m / 197e12,
                   4 * (rows * k + k * m + rows * m) / 819e9)
               for k, m in shapes)


def test_matmul_roofline_from_scopes_and_the_window_s_forwards(monkeypatch):
    monkeypatch.setattr(program, "program_spans", _program_spans)
    got = SPEC.reader("kernel.matmul_roofline")(_run(_records()))
    # decode 2x64 runs 2 rows, prefill 1x16 16; the 4x64 decode is after
    # the window; 3.5 ms of the window's device time is under the scopes,
    # 0.5 ms streams rows of the 32x64 MLP weight
    assert got == pytest.approx(100 * (_least_s(2) + _least_s(16)) / 0.004,
                                rel=1e-12)
    assert 0 < got <= 100


def test_matmul_roofline_finds_nothing_to_read(monkeypatch):
    monkeypatch.setattr(program, "program_spans", lambda: None)
    read = SPEC.reader("kernel.matmul_roofline")
    assert read(_run(_records())) is None          # an older program
    monkeypatch.setattr(program, "program_spans", _program_spans)
    rec = _records()
    for op in rec["ops"]["/device:TPU:0"]:
        op[3] = ""                                 # a trace without scopes
    assert read(_run(rec)) is None
    assert read(_run(None)) is None                # an untraced run


@dataclasses.dataclass
class _Req:
    tokens: list


@dataclasses.dataclass
class _Window:
    t0: float
    t1: float
    records: list
    counters: dict


def test_d2h_bytes_per_token_reads_the_program_s_counter():
    """The window's difference of ``SolServer.stats["d2h_bytes"]`` over the
    tokens it served; nothing where the program counts none."""
    w = _Window(0.0, 1.0, [_Req([0.1, 0.5, 1.5]), _Req([0.2, 0.3])],
                {"d2h_bytes": 2048.0, "h2d_bytes": 7.0})
    run = RunData(cell=_Cell(LM), window=w, spans=[], setup={},
                  records=None, summary=None, peaks=None)
    read = SPEC.reader("stage.d2h_bytes_per_token")
    assert read(run) == 512.0
    w.counters = {"h2d_bytes": 7.0}
    assert read(run) is None


FIXTURE = REPO / "bench/fixtures/trace_v5e_qwen2w_chat_scopes.json.gz"
QWEN = lm_widths(SPEC.config("qwen2-1.5b-widths"))


def test_recorded_v5e_trace_with_scopes(monkeypatch):
    """About 0.6 s of a traced ``qwen2w-chat`` window on one v5e, with each
    device operation's scope, the benchmark's ``bench.step`` spans on both
    clocks and the program's spans: the share reads between 0 and 100 %
    and agrees with a count by hand of the slice's forwards and of the
    time under the matmul family's scopes and its weights' scopes, and of
    the unscoped copies of its weights' rows."""
    fix = trace.read(str(FIXTURE))
    rec = {"ops": fix["ops"], "spans": fix["spans"]}
    kinds = {m for o in rec["ops"]["/device:TPU:0"]
             for m in re.findall(r"(?:^|/)([a-z_]+):", o[3])}
    assert {"linear", "matmul", "decode_attention", "layernorm"} <= kinds
    monkeypatch.setattr(program, "program_spans",
                        lambda: [tuple(s) for s in fix["program"]])
    run = RunData(cell=_Cell(QWEN), window=None,
                  spans=[tuple(s) + (None,) for s in fix["host_steps"]],
                  setup={}, records=rec, summary=None, peaks=V5E)
    got = SPEC.reader("kernel.matmul_roofline")(run)
    d, f, v, kv = 1536, 8960, 151936, 256
    shapes = [(d, d), (d, kv), (d, kv), (d, d), (d, f), (f, d)] * 28 \
        + [(d, v)]
    rows = [int(b) * (int(s) if n == "sol.prefill" else 1)
            for n, *_, attrs in fix["program"]
            if n in ("sol.prefill", "sol.decode")
            for b, s in [attrs["bucket"].split("x")]]
    least = sum(max(2 * r * k * m / 197e12,
                    4 * (r * k + k * m + r * m) / 819e9)
                for r in rows for k, m in shapes)
    w0, w1 = trace.window_of(rec)
    seconds = 1e-9 * sum(
        dur for _, start, dur, scope in rec["ops"]["/device:TPU:0"]
        if w0 <= start < w1 and re.search(
            r"(^|/)(linear|matmul):|\['\d+\.(0\.1\.w[qkvo]|1\.[13]\.weight"
            r"|weight)'\]", scope))
    # unscoped copies of the weights' rows: wq/wo, wk/wv, the MLP, the head
    mats = {(d, d), (d, kv), (f, d), (d, f), (v, d)}
    streamed = 1e-9 * sum(
        dur for name, start, dur, scope in rec["ops"]["/device:TPU:0"]
        for m in [re.match(r"(async|copy)-(start|update|done) \(*f32\[(\d+),"
                           r"(\d+)\]", name)]
        if w0 <= start < w1 and not scope and m and any(
            int(m[4]) == c and int(m[3]) <= r for r, c in mats))
    assert rows and seconds > 0 and streamed > 0
    seconds += streamed
    assert got == pytest.approx(100 * least / seconds, rel=1e-9)
    assert 0 < got <= 100
