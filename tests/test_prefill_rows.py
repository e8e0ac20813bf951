"""The prefill program keeps each entry's last row before the head.

``extract_prefill`` gives the program an int32 ``last`` input and a
``TAKE_ROWS`` node ahead of the model's position-wise suffix, so the head
runs on one row per entry and the server fetches ``(B, 1, V)`` logits:
the kept rows equal the all-position forward's, one program serves every
prompt length of its bucket, a prefill brings one logits row per entry to
the host, and the span and stats say how many rows the head ran on.  The
op itself: its lowering, its cost, its place as a fusion barrier and its
sharding rule."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.backends import get_backend
from repro.core import autotune as AT
from repro.core import passes
from repro.core.executor import lower_graph
from repro.core.ir import Graph, Node, OpKind, TensorSpec, const_node, \
    input_node
from repro.distributed import sharding as shd
from repro.frontends import nn
from repro.frontends.extract import extract_prefill, position_wise_suffix
from repro.frontends.offload import device
from repro.frontends.optimize import compile_graph, optimize
from repro.launch.serve import ServeConfig, SolServer, build_lm
from repro.runtime import telemetry

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench"))

from harness import model as bench_model  # noqa: E402
from harness import weights  # noqa: E402
from harness.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
# the two served architectures at widths a CPU test affords
TINY = {
    "pre_ln_gelu_lm": {
        "d_model": 32, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 64, "vocab": 64, "dtype": "float32",
        "matmul_precision": "default", "model": "pre_ln_gelu_lm",
        "reference": "pre_ln_gelu_lm"},
    "mellum2_moe_lm": {
        "d_model": 32, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "n_experts": 8, "n_held": 3, "first_expert": 2,
        "d_expert": 24, "top_k": 3, "norm_topk": True, "router": "softmax",
        "rms_eps": 1e-6, "vocab": 64,
        "period": ["sliding", "sliding", "sliding", "full"], "window": 4,
        "rope_theta": 500000,
        "yarn": {"factor": 4, "original_max_position_embeddings": 16,
                 "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.1},
        "dtype": "float32", "matmul_precision": "default",
        "model": "mellum2_moe_lm", "reference": "mellum2_moe_lm"},
}
# the head, and the final RMSNorm before Mellum2's
SUFFIX = {"pre_ln_gelu_lm": 1, "mellum2_moe_lm": 2}
LIMITS = {"max_seq": 32, "max_batch": 4, "slots": 4}
SEED = 2**33 + 17


@pytest.fixture(autouse=True)
def _native_mode_and_local_cache():
    device.set("cpu", 0, mode="native")
    prev = AT.get_cache()
    AT.set_cache(AT.AutotuneCache())
    yield
    AT.set_cache(prev)


def _served(name):
    """The architecture's seeded model and its server, over ``LIMITS``."""
    arch, lm = SPEC.model(name), TINY[name]
    m = bench_model.build_seeded(arch, lm, SEED)
    srv = bench_model.server(arch, lm, LIMITS, m,
                             weights.embedding(lm, SEED), "xla")
    srv.strict_provenance = False
    return m, srv


def _prompts(vocab, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in lengths]


@pytest.mark.parametrize("name", sorted(TINY))
def test_served_last_logits_match_the_all_position_forward(name):
    """Three prompts of 3, 9 and 14 tokens share the (4, 16) bucket with one
    padding entry: each request's first-token logits equal its last row of
    the plain all-position forward over the same padded batch, and the
    padding entry's row 0 comes out as that forward's row 0."""
    m, srv = _served(name)
    lens = [3, 9, 14]
    reqs = [srv.submit(p, 2) for p in _prompts(TINY[name]["vocab"], lens)]
    srv.step()
    assert srv.stats["buckets"] == {"4x16": 1}
    # the padded batch as the server builds it: token 0 past each prompt,
    # and a zero entry
    x = np.zeros((4, 16, TINY[name]["d_model"]), np.float32)
    for i, r in enumerate(reqs):
        padded = np.zeros(16, np.int32)
        padded[:len(r.prompt)] = r.prompt
        x[i] = srv.embed[padded]
    full = np.asarray(optimize(m, x.shape, backend="xla")(jnp.asarray(x)))
    for i, (r, n) in enumerate(zip(reqs, lens)):
        np.testing.assert_allclose(r.last_logits, full[i, n - 1],
                                   rtol=1e-5, atol=1e-5)
    last = np.array([n - 1 for n in lens] + [0], np.int32)
    out = srv._models[("prefill", 4, 16)](jnp.asarray(x), jnp.asarray(last))
    got = np.asarray(out[0])
    assert got.shape == (4, 1, TINY[name]["vocab"])
    np.testing.assert_allclose(got[:, 0], full[np.arange(4), last],
                               rtol=1e-5, atol=1e-5)
    srv.run()
    srv.close()


@pytest.mark.parametrize("name", sorted(TINY))
def test_head_runs_on_one_row_per_entry(name):
    """The rows are taken before the model's position-wise suffix: every
    node after ``TAKE_ROWS`` (the head, and a final norm) is ``(B, 1, .)``
    wide, and the cache rows still cover every position."""
    m = bench_model.build_seeded(SPEC.model(name), TINY[name], SEED)
    d, v = TINY[name]["d_model"], TINY[name]["vocab"]
    assert position_wise_suffix(m) == SUFFIX[name]
    g = extract_prefill(m, (2, 16, d))
    assert [(i.name, i.spec.shape, i.spec.dtype) for i in g.inputs] == [
        ("input", (2, 16, d), "float32"), ("last", (2,), "int32")]
    take, = g.nodes_of(OpKind.TAKE_ROWS)
    assert take.spec.shape == (2, 1, d) and take.inputs[1] is g.inputs[1]
    assert g.outputs[0].spec.shape == (2, 1, v)
    assert g.head_rows == 2
    head = g.outputs[0]
    if head.op is OpKind.BIAS_ADD:
        head = head.inputs[0]
    assert head.op is OpKind.LINEAR and head.inputs[0].spec.shape == (2, 1, d)
    assert all(o.spec.shape[:2] == (2, 16) for o in g.outputs[1:]
               if o.op is not OpKind.STACK)


def test_a_model_without_a_position_wise_suffix_takes_its_output_rows():
    """A top level that is not a plain chain, or a chain that ends in
    attention, has its output's rows taken: still one row per entry."""
    d = 16
    ends_in_attention = nn.Sequential(nn.Linear(d, d),
                                      nn.MultiHeadAttention(d, 2))
    residual_root = nn.Residual(nn.LayerNorm(d), nn.Linear(d, d))
    x = np.random.default_rng(1).standard_normal((2, 8, d)).astype("float32")
    last = np.array([5, 2], np.int32)
    for m in (ends_in_attention, residual_root):
        assert position_wise_suffix(m) == 0
        g = extract_prefill(m, (2, 8, d))
        assert g.outputs[0].op is OpKind.TAKE_ROWS and g.head_rows == 16
        got = compile_graph(m, g, "xla")(x, last)
        got = np.asarray(got[0] if isinstance(got, tuple) else got)
        full = np.asarray(optimize(m, (2, 8, d), backend="xla")(x))
        np.testing.assert_allclose(got[:, 0], full[np.arange(2), last],
                                   rtol=1e-5, atol=1e-5)


def _compile_events():
    """JAX compile events so far, counted as the benchmark harness counts
    them (one process-wide listener)."""
    if not _EVENTS["listening"]:
        def on_event(event, _duration, **_kw):
            if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                         "/jax/core/compile/backend_compile_duration"):
                _EVENTS["n"] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        _EVENTS["listening"] = True
    return _EVENTS["n"]


_EVENTS = {"n": 0, "listening": False}


@pytest.mark.parametrize("name", sorted(TINY))
def test_every_prompt_length_of_a_bucket_runs_its_one_program(name):
    """After the (1, 16) and (2, 16) buckets have run once, prompts of
    every length from 9 to 16 tokens, alone and in pairs, build no program
    and compile nothing: the last position is a traced input."""
    _, srv = _served(name)
    vocab = TINY[name]["vocab"]
    for group in ([12], [10, 13]):
        for p in _prompts(vocab, group):
            srv.submit(p, 1)
        srv.run()
    models, compiles = dict(srv._models), srv.stats["compiles"]
    events = _compile_events()
    for n in range(9, 17):
        for p in _prompts(vocab, [n] if n % 2 else [n, 25 - n], seed=n):
            srv.submit(p, 1)
        srv.run()
    assert srv._models == models and srv.stats["compiles"] == compiles
    assert _compile_events() == events
    assert set(k for k in srv._models if k[0] == "prefill") == {
        ("prefill", 1, 16), ("prefill", 2, 16)}
    srv.close()


@pytest.mark.parametrize("name", sorted(TINY))
def test_prefill_fetches_one_logits_row_per_entry(name):
    """A prefill step of three prompts in a batch bucket of 4 brings
    ``4 x vocab`` float32 logits to the host, plus the expert layers'
    counters where the model has them, and its span says so."""
    _, srv = _served(name)
    lm = TINY[name]
    for p in _prompts(lm["vocab"], [4, 7, 6]):
        srv.submit(p, 3)
    before = srv.stats["d2h_bytes"]
    srv.step()
    counters = 2 * 4 * lm["n_layers"] if name == "mellum2_moe_lm" else 0
    assert srv.stats["d2h_bytes"] - before == 4 * lm["vocab"] * 4 + counters
    fetch = [r for r in telemetry.spans() if r[0] == "sol.fetch"][-1]
    assert fetch[4]["bytes"] == 4 * lm["vocab"] * 4 + counters
    srv.run()
    srv.close()


def test_head_rows_count_the_rows_the_suffix_ran_on():
    """``sol.prefill`` carries ``head_rows``, the batch bucket when the
    head runs after the row selection, and ``stats['prefill_head_rows']``
    sums it; against ``prefill_positions`` it is the suffix's remaining
    share of the work."""
    cfg = ServeConfig(d_model=32, n_heads=2, n_layers=1, vocab=64,
                      max_seq=32, max_batch=2, slots=3, backend="xla")
    srv = SolServer(cfg)
    t0 = time.perf_counter()
    for p in ([1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11, 12], [7] * 20):
        srv.submit(p, 2)
    srv.run()
    recs = [r for r in telemetry.spans()
            if r[0] == "sol.prefill" and r[1] >= t0]
    buckets = [tuple(int(v) for v in r[4]["bucket"].split("x"))
               for r in recs]
    assert [r[4]["head_rows"] for r in recs] == [b for b, _ in buckets]
    assert srv.stats["prefill_head_rows"] == sum(b for b, _ in buckets)
    assert srv.stats["prefill_positions"] == sum(b * s for b, s in buckets)
    assert srv.stats["prefill_head_rows"] < srv.stats["prefill_positions"]
    srv.close()

    # a model whose head follows attention directly has no suffix to spare:
    # its head ran on every position, and the count says so
    plain = SolServer(cfg, nn.Sequential(build_lm(cfg).mods[0]))
    plain.submit([1, 2, 3], 1)
    plain.run()
    assert plain.stats["prefill_head_rows"] == plain.stats[
        "prefill_positions"] == 8
    plain.close()


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def _take_graph(shape=(3, 5, 4)):
    x = input_node(shape, name="x")
    idx = input_node(shape[:1], "int32", name="idx")
    relu = Node(OpKind.RELU, [x], TensorSpec(shape))
    take = Node(OpKind.TAKE_ROWS, [relu, idx],
                TensorSpec((shape[0], 1) + shape[2:]))
    out = Node(OpKind.TANH, [take], take.spec)
    return Graph(inputs=[x, idx], outputs=[out], params={}), take


def test_take_rows_lowers_to_each_entry_s_row():
    g, _ = _take_graph()
    g = passes.run_pipeline(g, get_backend("xla"))
    fn = jax.jit(lower_graph(g, get_backend("xla")))
    x = np.random.default_rng(2).standard_normal((3, 5, 4)).astype("float32")
    idx = np.array([4, 0, 2], np.int32)
    got = np.asarray(fn({}, x, idx))
    np.testing.assert_allclose(got[:, 0],
                               np.tanh(np.maximum(x, 0)[np.arange(3), idx]),
                               rtol=1e-6, atol=1e-6)
    # an index past the rows reads NaN, never a clamped row
    bad = np.asarray(fn({}, x, np.array([5, 0, 2], np.int32)))
    assert np.isnan(bad[0]).all() and not np.isnan(bad[1:]).any()


def test_take_rows_is_a_fusion_barrier_costed_as_bytes_moved():
    g, take = _take_graph()
    g = passes.run_pipeline(g, get_backend("xla"))
    assert take in g.topo() and take.op is OpKind.TAKE_ROWS
    assert not any(take in f.body for f in g.nodes_of(OpKind.FUSED))
    assert take.impl == "ref.take_rows"
    flops, streamed, roundtrip = passes._node_cost_terms(take)
    # three rows of 4 float32 read and written, and three int32 indices
    assert (flops, streamed, roundtrip) == (0.0, 2 * 3 * 4 * 4 + 3 * 4,
                                            2 * 3 * 4 * 4 + 3 * 4)


def test_take_rows_sharding_follows_the_batch():
    """On a (data 2, model 2) mesh the prefill program's selection keeps
    the batch's data shard, whole rows and features, and its indices are
    split like the batch; indices replicated against a split batch have no
    rule."""
    cfg = ServeConfig(d_model=32, n_heads=2, n_layers=1, vocab=64)
    mesh = AbstractMesh((2, 2), ("data", "model"))
    g = shd.shard_graph(extract_prefill(build_lm(cfg), (4, 8, 32)), mesh)
    take, = g.nodes_of(OpKind.TAKE_ROWS)
    assert take.spec.shape == (2, 1, 32)
    assert g.input_specs[1] == g.input_specs[0][:1]
    assert g.input_specs[0][0] is not None
    assert g.outputs[0].spec.shape == (2, 1, 32)      # vocab-parallel head

    x = input_node((4, 8, 32), name="x")
    fixed = const_node((4,), 0.0, "int32")
    bad = Node(OpKind.TAKE_ROWS, [x, fixed], TensorSpec((4, 1, 32)))
    with pytest.raises(shd.ShardingError, match="indices"):
        shd.shard_graph(Graph(inputs=[x], outputs=[bad], params={}), mesh)
