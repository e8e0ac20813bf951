"""The Mellum2 expert-parallel share (``bench/models/mellum2_moe_lm.py``)
at tiny widths on the CPU: served through ``SolServer`` against its plain
reference, run as a cell of the harness, its work counted, and the
expert layer's roofline share read from a hand-made trace."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest
from benchkit import REPO, tiny_mix

from harness import model, peaks, program, weights
from harness.readers import RunData
from harness.spec import Spec, lm_widths

from harness_run import run_cell

SPEC = Spec(REPO)
ARCH = SPEC.model("mellum2_moe_lm")
REF = SPEC.reference("mellum2_moe_lm")
# four heads of 16 over a width of 32 (head_dim apart from d / heads), a
# window of 4 that prompts and answers reach past, 3 of 8 experts held
TINY = {"d_model": 32, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "n_experts": 8, "n_held": 3, "first_expert": 2,
        "d_expert": 24, "top_k": 3, "norm_topk": True, "router": "softmax",
        "rms_eps": 1e-6, "vocab": 64,
        "period": ["sliding", "sliding", "sliding", "full"], "window": 4,
        "rope_theta": 500000,
        "yarn": {"factor": 4, "original_max_position_embeddings": 16,
                 "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.1},
        "dtype": "float32", "matmul_precision": "default",
        "model": "mellum2_moe_lm", "reference": "mellum2_moe_lm"}
CONFIG = lm_widths(json.loads(
    (REPO / "bench/configs/mellum2-12b-a2.5b-ep4.json").read_text()))


def _serve(lm, seed, prompts, gen, limits, backend="xla"):
    m = model.build_seeded(ARCH, lm, seed)
    srv = model.server(ARCH, lm, limits, m, weights.embedding(lm, seed),
                       backend)
    reqs = [srv.submit(p, gen) for p in prompts]
    srv.warm_autotune()
    seen = {r.rid: [] for r in reqs}
    while srv.depth:
        for rid in srv.step():
            r = next(x for x in reqs if x.rid == rid)
            seen[rid].append(np.asarray(r.last_logits, np.float64))
    srv.close()
    return reqs, seen


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_served_prefill_and_decode_logits_match_the_reference(
        fast_autotune, backend):
    """Prompts of 3 to 11 tokens and 6 decoded tokens, past the sliding
    layers' window of 4 both in the prefill and in decode through the
    arena."""
    seed = 2**33 + 9
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY["vocab"], n, dtype=np.int32)
               for n in (5, 11, 3)]
    reqs, seen = _serve(TINY, seed, prompts, 6,
                        {"max_seq": 32, "max_batch": 2, "slots": 3}, backend)
    params = weights.make_params(ARCH.weights(TINY), seed)
    embed = weights.embedding(TINY, seed)
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1])])
        ref = np.asarray(REF.logits(params, TINY, embed[seq]), np.float64)
        pos = np.arange(len(r.prompt) - 1, len(seq))
        got = np.stack(seen[r.rid])
        scale = np.abs(ref[pos]).max()
        # float32 on the CPU: only the order of summation differs
        assert np.abs(got - ref[pos]).max() <= 1e-4 * scale
        assert (got.argmax(-1) == r.generated).all()
        low = np.asarray(REF.logits(params, TINY, embed[seq],
                                    dtype=jnp.bfloat16), np.float64)
        assert np.abs(low[pos] - ref[pos]).max() > 10 * 1e-4 * scale


def test_the_reference_drops_no_window_and_no_expert():
    """The reference's windows and expert share reach its logits: one that
    attends every position, or adds every expert, reads otherwise."""
    seed = 3
    params = weights.make_params(ARCH.weights(TINY), seed)
    rows = weights.embedding(TINY, seed)[np.arange(12)]
    base = np.asarray(REF.logits(params, TINY, rows))
    wide = np.asarray(REF.logits(params, dict(TINY, window=64), rows))
    assert np.abs(wide - base)[:12].max() > 1e-3
    other = np.asarray(REF.logits(params, dict(TINY, first_expert=0), rows))
    assert np.abs(other - base)[:12].max() > 1e-3


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_runs_at_tiny_widths(checkout, fast_autotune, capsys, trace):
    base = json.loads((REPO / "bench/traffic/code-chat-poisson.json")
                      .read_text())
    checkout.add_cell("tiny-mellum", "tiny-mellum-cfg", "tiny-code", lm=TINY,
                      mix=tiny_mix(base))
    line = run_cell(checkout, "tiny-mellum", capsys, trace=trace)
    assert line["correct"] is True
    assert line["check"]["tokens_checked"]["value"] >= 4
    if trace == "1":
        assert {"sched.host_ms_per_step", "stage.h2d_bytes_per_token"} \
            <= set(line["metrics"])


def test_forward_spans_carry_the_expert_counters(fast_autotune):
    """Each prefill and decode span carries ``moe_pairs`` and
    ``moe_touched``, summed over the layers: at most ``top_k`` pairs a row
    and ``n_held`` experts a layer, as the routing in the reference
    gives them."""
    from repro.runtime import telemetry
    seed = 11
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, TINY["vocab"], n, dtype=np.int32)
               for n in (6, 9)]
    _serve(TINY, seed, prompts, 3, {"max_seq": 32, "max_batch": 2,
                                    "slots": 2})
    fwd = [s for s in telemetry.spans() if s[0] in ("sol.prefill",
                                                    "sol.decode")][-4:]
    assert fwd
    n, k, held = TINY["n_layers"], TINY["top_k"], TINY["n_held"]
    for name, _, _, _, a in fwd:
        b, s = (int(v) for v in a["bucket"].split("x"))
        rows = b * s if name == "sol.prefill" else b
        assert 0 < a["moe_pairs"] <= n * rows * k
        assert 0 < a["moe_touched"] <= min(n * held, a["moe_pairs"])


def test_work_counts_add_up():
    """``kernel_work`` holds the head and the projections (``linear``,
    ``matmul``) and the experts at the expected held pairs (``moe``),
    which with the router make ``decode_flops`` without its attention; every
    expert weight is named, and read once."""
    lm = CONFIG
    d, n = lm["d_model"], lm["n_layers"]
    work = [w for op in ("linear", "matmul", "moe")
            for w in ARCH.kernel_work(lm, op, "decode", 1, 512)]
    attn = 4.0 * lm["n_heads"] * lm["head_dim"] * n
    router = 2.0 * d * lm["n_experts"] * n
    assert sum(f for f, _, _ in work) + attn + router == pytest.approx(
        ARCH.decode_flops(lm, 0), rel=1e-12)
    table = weights.shapes(ARCH.weights(lm))
    experts = sorted(k for k, s in table.items() if len(s) == 3)
    assert sorted(w for _, _, w in ARCH.kernel_work(lm, "moe", "decode", 8,
                                                    1)) == experts
    # 1.19 B parameters, 4.76 GB in float32 (the configuration's size)
    size = sum(int(np.prod(s)) for s in table.values())
    assert 1.18e9 < size < 1.20e9
    # sliding layers attend at most the window; full layers every position
    assert ARCH.prefill_flops(lm, 2048) - ARCH.prefill_flops(lm, 2047) < \
        ARCH.decode_flops(lm, 2047) + 1


# -- kernel.moe_roofline on a hand-made trace ---------------------------------

MS = 1_000_000
V5E = peaks.peaks("TPU v5 lite")


@dataclasses.dataclass
class _Cell:
    lm: dict
    arch: object = ARCH


def _records():
    """A 10 ms window with two steps: 1 ms under ``moe:`` scopes (and 1 ms
    before the window), 0.5 ms under ``moe_count:``, 0.5 ms under an
    expert weight's scope, 0.5 ms of an unscoped copy of some of a layer's
    experts, 1 ms under ``matmul:`` and 0.25 ms of an unscoped copy of an
    attention weight's rows."""
    return {"spans": [["bench.window", 0, 10 * MS], ["bench.step", 0, 5 * MS],
                      ["bench.step", 6 * MS, 3 * MS]],
            "ops": {"/device:TPU:0": [
                ["x", -2 * MS, 1 * MS, "jit(run)/moe:pallas.moe_gmm"],
                ["a", 1 * MS, 1 * MS, "jit(run)/jit(fn)/moe:pallas.moe_gmm/d"],
                ["c", 2 * MS, MS // 2, "jit(run)/moe_count:ref.moe_count"],
                ["w", 3 * MS, MS // 2, "params['1.1.1.w_up']:"],
                ["%async-done.4 = f32[2,32,24]{2,1,0} async-done(%s)",
                 4 * MS, MS // 2, ""],
                ["m", 5 * MS, 1 * MS, "jit(run)/matmul:ref.matmul"],
                ["%async-done.5 = f32[16,64]{1,0} async-done(%t)",
                 7 * MS, MS // 4, ""]]}}


def _program_spans():
    """On the host clock, 100 s behind the trace's: a decode at bucket 2x64
    and a prefill at 1x16 in the window, a decode after it."""
    return [("sol.step", 100.000, 100.005, None, {}),
            ("sol.decode", 100.001, 100.004, 0,
             {"bucket": "2x64", "moe_pairs": 9, "moe_touched": 7}),
            ("sol.step", 100.006, 100.009, None, {}),
            ("sol.prefill", 100.0065, 100.0085, 2,
             {"bucket": "1x16", "moe_pairs": 70, "moe_touched": 12}),
            ("sol.step", 100.020, 100.021, None, {}),
            ("sol.decode", 100.0201, 100.0205, 4,
             {"bucket": "4x64", "moe_pairs": 20, "moe_touched": 12})]


def _run(records):
    host_steps = [("bench.step", 100.000, 100.005, None),
                  ("bench.step", 100.006, 100.009, None)]
    return RunData(cell=_Cell(lm_widths({"lm": TINY})), window=None,
                   spans=host_steps, setup={}, records=records, summary=None,
                   peaks=V5E)


def test_moe_roofline_from_counters_and_scopes(monkeypatch):
    monkeypatch.setattr(program, "program_spans", _program_spans)
    got = SPEC.reader("kernel.moe_roofline")(_run(_records()))
    d, f, n = 32, 24, 4

    def least(rows, pairs, touched):
        return max(6 * d * f * pairs / 197e12,
                   4 * (3 * d * f * touched + 2 * n * rows * d) / 819e9)
    # the 4x64 decode is after the window; 2 ms under the scopes and the
    # expert weight's, 0.5 ms copying experts; the attention weight's copy
    # and the matmul are not the expert layer's
    assert got == pytest.approx(
        100 * (least(2, 9, 7) + least(16, 70, 12)) / 0.0025, rel=1e-12)
    assert 0 < got <= 100


def test_moe_roofline_finds_nothing_to_read(monkeypatch):
    read = SPEC.reader("kernel.moe_roofline")
    monkeypatch.setattr(program, "program_spans", lambda: None)
    assert read(_run(_records())) is None          # an older program
    monkeypatch.setattr(program, "program_spans", lambda: [
        (n, a, b, p, {k: v for k, v in attrs.items() if "moe" not in k})
        for n, a, b, p, attrs in _program_spans()])
    assert read(_run(_records())) is None          # a program without them
    assert read(_run(None)) is None                # an untraced run
