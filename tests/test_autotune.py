"""Autotune subsystem tests: cache round-trip + schema/atomicity guarantees,
shape-bucket canonicalization with nearest-bucket lookup (plus hypothesis
property tests over both), measured-first election (provenance, config
pinning through the Tunable protocol, the roofline-contradicting flip), the
calibration fits (roofline coefficients and the DFP _EW_FLOPS constant),
and the MXU matmul as the elected LINEAR/MATMUL flavour."""
import json
import os

from _hypo import hypothesis, st  # real hypothesis, or skip-stubs when absent
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import get_backend
from repro.backends import registry as R
from repro.core import autotune, ir, passes
from repro.core.autotune import (AutotuneCache, Measurement, bucket_dim,
                                 bucket_shape)
from repro.core.executor import lower_graph
from repro.core.ir import Graph, Node, OpKind, TensorSpec
from repro.frontends import nn
from repro.frontends.optimize import optimize


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Every test starts (and leaves the process) with a cold session cache.
    An explicit empty AutotuneCache, not set_cache(None): None means 'reset
    to default', which would re-read SOL_AUTOTUNE_CACHE from the env."""
    autotune.set_cache(AutotuneCache())
    yield
    autotune.set_cache(AutotuneCache())


def _linear_graph(b=2, d_in=16, d_out=32):
    x = ir.input_node((b, d_in), name="x")
    w = ir.param_node((d_out, d_in), name="w")
    lin = Node(OpKind.LINEAR, [x, w], TensorSpec((b, d_out)),
               attrs={"out_features": d_out})
    return Graph([x], [lin], {"w": w}), lin


# -- cache mechanics -----------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    """save → load preserves measurements, configs, and calibration."""
    path = str(tmp_path / "cache.json")
    c = AutotuneCache()
    c.record("matmul", (256, 256, 256), "float32", "pallas_tpu",
             "pallas.matmul_mxu", 12.5, config=(128, 128, 128),
             flops=2 * 256 ** 3, nbytes=3 * 256 * 256 * 4)
    c.record("matmul", (256, 256, 256), "float32", "pallas_tpu",
             "ref.matmul", 20.0)
    c.set_calibration("pallas_tpu", "matmul",
                      {"s_per_flop": 1e-14, "s_per_byte": 2e-12, "n": 2.0})
    c.save(path)
    assert not any(f.endswith(".tmp") for f in os.listdir(tmp_path))

    c2 = AutotuneCache.load(path)
    assert not c2.stale
    got = c2.lookup("matmul", (256, 256, 256), "float32", "pallas_tpu")
    assert got["pallas.matmul_mxu"].us == 12.5
    assert got["pallas.matmul_mxu"].config == (128, 128, 128)
    assert got["ref.matmul"].us == 20.0
    assert c2.calibration("pallas_tpu", "matmul")["s_per_flop"] == 1e-14


def test_stale_schema_ignored_not_misread(tmp_path):
    """A cache written by a different schema version comes back empty with
    stale=True — old files are never misinterpreted."""
    path = tmp_path / "old.json"
    path.write_text(json.dumps({
        "schema": autotune.SCHEMA_VERSION + 1,
        "entries": {"matmul|float32|xla|256x256x256":
                    {"ref.matmul": {"us": 1.0}}}}))
    c = AutotuneCache.load(str(path))
    assert c.stale
    assert len(c) == 0
    assert c.lookup("matmul", (256, 256, 256), "float32", "xla") == {}


def test_corrupt_file_yields_empty_cache(tmp_path):
    path = tmp_path / "torn.json"
    path.write_text('{"schema": 1, "entr')      # torn write simulation
    c = AutotuneCache.load(str(path))
    assert len(c) == 0 and not c.stale


def test_record_keeps_best_time():
    c = AutotuneCache()
    c.record("matmul", (64, 64, 64), "float32", "xla", "ref.matmul", 9.0)
    c.record("matmul", (64, 64, 64), "float32", "xla", "ref.matmul", 5.0,
             config=(32, 32, 32))
    c.record("matmul", (64, 64, 64), "float32", "xla", "ref.matmul", 7.0)
    m = c.lookup("matmul", (64, 64, 64), "float32", "xla")["ref.matmul"]
    assert m.us == 5.0 and m.config == (32, 32, 32)


def test_bucket_canonicalization_and_nearest_lookup():
    """Shapes bucket to nearest powers of two; unseen buckets resolve to the
    nearest same-rank bucket in log2-space."""
    assert bucket_shape((100, 70, 36)) == (128, 64, 32)
    c = AutotuneCache()
    c.record("matmul", (256, 256, 256), "float32", "xla", "ref.matmul", 3.0)
    c.record("matmul", (2048, 2048, 2048), "float32", "xla", "ref.matmul",
             90.0)
    # same bucket (250→256)
    assert c.lookup("matmul", (250, 260, 255), "float32", "xla")[
        "ref.matmul"].us == 3.0
    # unseen bucket (4096) → nearest is 2048
    assert c.lookup("matmul", (4096, 4096, 4096), "float32", "xla")[
        "ref.matmul"].us == 90.0
    # other backend/dtype/op stay isolated
    assert c.lookup("matmul", (256, 256, 256), "bfloat16", "xla") == {}
    assert c.lookup("matmul", (256, 256, 256), "float32", "host_cpu") == {}
    assert c.lookup("linear", (256, 256, 256), "float32", "xla") == {}


# -- hypothesis property tests ---------------------------------------------------

@hypothesis.settings(max_examples=100, deadline=None)
@hypothesis.given(a=st.integers(1, 1 << 20), b=st.integers(1, 1 << 20))
def test_bucket_dim_monotone_pow2(a, b):
    """bucket_dim is monotone, always a power of two, within a ×√2 factor
    of its argument, and bucket_shape applies it elementwise."""
    lo, hi = sorted((a, b))
    assert bucket_dim(lo) <= bucket_dim(hi)
    for d in (a, b):
        bd = bucket_dim(d)
        assert bd >= 1 and (bd & (bd - 1)) == 0
        assert bd / d <= 2 ** 0.5 + 1e-9 and d / bd <= 2 ** 0.5 + 1e-9
    assert bucket_shape((a, b)) == (bucket_dim(a), bucket_dim(b))


@hypothesis.settings(max_examples=50, deadline=None)
@hypothesis.given(
    shape=st.lists(st.integers(1, 4096), min_size=1, max_size=4),
    probe=st.lists(st.integers(1, 4096), min_size=1, max_size=4),
    us=st.floats(1e-3, 1e6, allow_nan=False, allow_infinity=False))
def test_lookup_never_crosses_ops_dtypes_backends(shape, probe, us):
    """Nearest-bucket lookup may roam across same-rank buckets but never
    across op kinds, dtypes, backends, or ranks."""
    c = AutotuneCache()
    c.record("matmul", tuple(shape), "float32", "xla", "ref.matmul", us)
    assert c.lookup("linear", tuple(probe), "float32", "xla") == {}
    assert c.lookup("matmul", tuple(probe), "bfloat16", "xla") == {}
    assert c.lookup("matmul", tuple(probe), "float32", "host_cpu") == {}
    got = c.lookup("matmul", tuple(probe), "float32", "xla")
    if len(probe) == len(shape):
        assert got["ref.matmul"].us == us     # the only same-rank bucket
    else:
        assert got == {}


_ENTRY = st.tuples(
    st.sampled_from(["matmul", "linear", "attention", "fused"]),
    st.lists(st.integers(1, 2048), min_size=1, max_size=4),
    st.sampled_from(["float32", "bfloat16"]),
    st.sampled_from(["xla", "host_cpu", "pallas_interpret"]),
    st.sampled_from(["ref.x", "pallas.y", "host_cpu.z"]),
    st.floats(1e-3, 1e6, allow_nan=False, allow_infinity=False),
    st.one_of(st.none(), st.lists(st.integers(1, 512), min_size=1,
                                  max_size=3)))


@hypothesis.settings(max_examples=25, deadline=None,
                     suppress_health_check=[
                         hypothesis.HealthCheck.function_scoped_fixture])
@hypothesis.given(entries=st.lists(_ENTRY, max_size=12))
def test_cache_save_load_roundtrip_idempotent(tmp_path, entries):
    """save → load reproduces the cache exactly, and a second save → load
    of the loaded cache is a fixed point (idempotence)."""
    c = AutotuneCache()
    for op, shape, dtype, backend, impl, us, cfg in entries:
        c.record(op, tuple(shape), dtype, backend, impl, us,
                 config=tuple(cfg) if cfg else None,
                 flops=us * 2, nbytes=us * 3)
    p1 = str(tmp_path / "c1.json")
    c.save(p1)
    c2 = AutotuneCache.load(p1)
    assert c2.to_json() == c.to_json()
    assert len(c2) == len(c)
    p2 = str(tmp_path / "c2.json")
    c2.save(p2)
    assert AutotuneCache.load(p2).to_json() == c2.to_json()


# -- the Tunable protocol --------------------------------------------------------

def _attention_graph(b=1, s=64, h=2, hd=16):
    q, k, v = (ir.input_node((b, s, h, hd), name=nm) for nm in "qkv")
    node = Node(OpKind.ATTENTION, [q, k, v], TensorSpec((b, s, h, hd)),
                attrs={"causal": True})
    return Graph([q, k, v], [node], {}), node


def test_registry_declares_tunables_for_kernel_families():
    """ISSUE tentpole: every Pallas kernel family — matmul, flash
    attention, dfp_fused, both recurrence scans and the Listing-3 avgpool —
    exposes a tune space through the registry, and bind_config pins/clears
    the declared node attr."""
    from benchmarks.autotune import _node
    R._load_entry_points()
    hw = get_backend("pallas_interpret").hw
    _g, attn = _attention_graph()
    _g2, lin = _linear_graph(8, 256, 128)
    for impl_name, node in (
            ("pallas.matmul_mxu", _node("matmul", (256, 256, 256))),
            ("pallas.linear_mxu", lin),
            ("pallas.flash_attention", attn),
            ("pallas.dfp_fused", _node("fused", (256, 128))),
            ("pallas.rglru_scan", _node("rglru_scan", (2, 32, 256))),
            ("pallas.rwkv6_scan", _node("rwkv6_scan", (1, 64, 2, 16))),
            ("pallas.avgpool", _node("avgpool", (1, 8, 14, 14)))):
        impl = R.get_impl(impl_name)
        assert impl is not None and impl.tunable is not None, impl_name
        space = impl.tunable.tune_space(node, hw)
        assert len(space) >= 2, (impl_name, space)
        impl.tunable.bind_config(node, space[0])
        assert tuple(node.attrs[impl.tunable.attr]) == tuple(space[0])
        impl.tunable.bind_config(node, None)
        assert impl.tunable.attr not in node.attrs


def test_measured_attention_election_pins_and_clears_block():
    """A measured attention win pins its (bq, bk) config under the generic
    Tunable attr; a cold re-election clears it."""
    c = AutotuneCache()
    c.record("attention", (1, 64, 2, 16), "float32", "pallas_interpret",
             "pallas.flash_attention", 3.0, config=(32, 64))
    c.record("attention", (1, 64, 2, 16), "float32", "pallas_interpret",
             "ref.attention", 9.0)
    autotune.set_cache(c)
    g, node = _attention_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert node.impl == "pallas.flash_attention"
    assert node.attrs["attn_block"] == (32, 64)
    assert g.election_pinned["pallas.flash_attention"] == [(32, 64)]

    autotune.set_cache(AutotuneCache())
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert "attn_block" not in node.attrs


def _decode_graph(b=1, s=64, h=2, kv=2, hd=16):
    ins = [ir.input_node(shape) for shape in
           ((b, 1, h, hd), (b, s, kv, hd), (b, s, kv, hd), (b, 1, kv, hd),
            (b, 1, kv, hd))] + [ir.input_node((b,), "int32")]
    node = Node(OpKind.DECODE_ATTENTION, ins, TensorSpec((b, 1, h, hd)))
    return Graph(ins, [node], {}), node


@pytest.mark.parametrize("build,op,kernel,ref", [
    (_attention_graph, "attention", "pallas.flash_attention",
     "ref.attention"),
    (_decode_graph, "decode_attention", "pallas.decode_attention",
     "ref.decode_attention"),
], ids=["flash", "decode"])
def test_resident_kv_past_vmem_budget_elects_reference(build, op, kernel,
                                                       ref):
    """Where the whole-sequence K/V a kernel keeps resident exceeds the VMEM
    budget it compiles under, its ``supports`` declines and the reference is
    elected, even against a measurement that favours the kernel.  At
    hd=128 in f32 the double-buffered K/V of S=32768 alone fill the 64 MiB
    budget; at S=16384 the kernel still wins."""
    bk = R.tpu_backend("TPU v5 lite")
    for seq, want in ((32768, ref), (16384, kernel)):
        g, node = build(1, seq, 1, 1, 128) if op.startswith("decode") \
            else build(1, seq, 1, 128)
        c = AutotuneCache()
        c.record(op, autotune.node_shape(node), "float32", bk.cache_name,
                 kernel, 1.0)
        c.record(op, autotune.node_shape(node), "float32", bk.cache_name,
                 ref, 9.0)
        autotune.set_cache(c)
        passes.elect_implementations(g, bk)
        assert node.impl == want, (seq, node.impl)
        assert g.elections_by_op[op] == {want: 1}


def test_reelection_on_foreign_backend_clears_pin():
    """Re-electing on a backend where the tuned impl is inadmissible (no
    'pallas' capability on host_cpu) must still drop the stale pin."""
    c = AutotuneCache()
    c.record("attention", (1, 64, 2, 16), "float32", "pallas_interpret",
             "pallas.flash_attention", 3.0, config=(32, 64))
    autotune.set_cache(c)
    g, node = _attention_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert node.attrs["attn_block"] == (32, 64)

    passes.elect_implementations(g, get_backend("host_cpu"))
    assert node.impl == "ref.attention"
    assert "attn_block" not in node.attrs


def test_measured_attention_entry_flips_election():
    """ISSUE acceptance: a cached attention measurement flips the flavour
    choice — ref.attention wins only because the data says so."""
    g_cold, node_cold = _attention_graph()
    passes.elect_implementations(g_cold, get_backend("pallas_interpret"))
    assert node_cold.impl == "pallas.flash_attention"   # the roofline choice

    c = AutotuneCache()
    c.record("attention", (1, 64, 2, 16), "float32", "pallas_interpret",
             "pallas.flash_attention", 50.0, config=(64, 64))
    c.record("attention", (1, 64, 2, 16), "float32", "pallas_interpret",
             "ref.attention", 2.0)
    autotune.set_cache(c)
    g, node = _attention_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert node.impl == "ref.attention"
    assert g.election_provenance["ref.attention"] == {"measured": 1}
    assert "attn_block" not in node.attrs   # the loser's config is not pinned


def test_pinned_attention_block_executes_and_matches_reference():
    """End to end: elect with a warm cache, lower, execute — the pinned
    block size reaches the kernel and the output still matches the oracle."""
    from repro.kernels.flash_attention.ref import flash_attention_ref
    c = AutotuneCache()
    c.record("attention", (1, 64, 2, 16), "float32", "pallas_interpret",
             "pallas.flash_attention", 3.0, config=(32, 32))
    autotune.set_cache(c)
    g, node = _attention_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert node.attrs["attn_block"] == (32, 32)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
               for _ in range(3))
    y = lower_graph(g, get_backend("pallas_interpret"))({}, q, k, v)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -- measured election ----------------------------------------------------------

def test_cold_cache_falls_back_to_roofline():
    """ISSUE acceptance: a cold cache degrades gracefully to the analytical
    path — the MXU matmul wins on tier at equal roofline cost."""
    g, lin = _linear_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert lin.impl == "pallas.linear_mxu"
    assert g.election_provenance["pallas.linear_mxu"] == {"analytical": 1}


def test_warm_cache_election_uses_measurement(tmp_path):
    """save → load → election: the measured entry drives the choice and the
    provenance says so."""
    path = str(tmp_path / "cache.json")
    c = AutotuneCache()
    c.record("linear", (2, 16, 32), "float32", "pallas_interpret",
             "pallas.linear_mxu", 4.0, config=(16, 128, 128))
    c.record("linear", (2, 16, 32), "float32", "pallas_interpret",
             "ref.linear", 9.0)
    c.save(path)
    autotune.load_cache(path)

    g, lin = _linear_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert lin.impl == "pallas.linear_mxu"
    assert g.election_provenance["pallas.linear_mxu"] == {"measured": 1}
    # the winning measurement's tile config is pinned on the node
    assert lin.attrs["mxu_block"] == (16, 128, 128)


def test_reelection_clears_stale_tile_config():
    """A graph elected with a warm cache (pinned mxu_block) then re-elected
    cold must drop the stale tuned config — re-lowering on another backend
    or cache state is a supported flow."""
    c = AutotuneCache()
    c.record("linear", (2, 16, 32), "float32", "pallas_interpret",
             "pallas.linear_mxu", 4.0, config=(512, 256, 512))
    autotune.set_cache(c)
    g, lin = _linear_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert lin.attrs["mxu_block"] == (512, 256, 512)

    autotune.set_cache(AutotuneCache())
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert "mxu_block" not in lin.attrs

    # a measured winner without a config also clears a prior pin
    lin.attrs["mxu_block"] = (512, 256, 512)
    c2 = AutotuneCache()
    c2.record("linear", (2, 16, 32), "float32", "pallas_interpret",
              "ref.linear", 1.0)
    autotune.set_cache(c2)
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert lin.impl == "ref.linear" and "mxu_block" not in lin.attrs


def test_measured_entry_flips_roofline_choice():
    """ISSUE acceptance: a cache entry flips a flavour choice the roofline
    model would not make — ref.linear beats the MXU kernel only because the
    data says so."""
    g_cold, lin_cold = _linear_graph()
    passes.elect_implementations(g_cold, get_backend("pallas_interpret"))
    assert lin_cold.impl == "pallas.linear_mxu"       # the roofline choice

    c = AutotuneCache()
    c.record("linear", (2, 16, 32), "float32", "pallas_interpret",
             "pallas.linear_mxu", 50.0)
    c.record("linear", (2, 16, 32), "float32", "pallas_interpret",
             "ref.linear", 2.0)
    autotune.set_cache(c)
    g, lin = _linear_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert lin.impl == "ref.linear"
    assert g.election_provenance["ref.linear"] == {"measured": 1}


def test_impl_report_shows_measured_provenance():
    """ISSUE acceptance: with a warm cache, SolModel.impl_report() shows
    elections sourced from measurements."""
    model = nn.mlp_8192(2, 32, 16, 4)
    c = AutotuneCache()
    c.record("linear", (2, 16, 32), "float32", "pallas_interpret",
             "pallas.linear_mxu", 3.0)
    autotune.set_cache(c)
    sol = optimize(model, (2, 16), backend="pallas_interpret")
    report = sol.impl_report(provenance=True)
    assert report["pallas.linear_mxu"]["sources"].get("measured", 0) >= 1

    autotune.set_cache(AutotuneCache())               # cold again
    sol_cold = optimize(model, (2, 16), backend="pallas_interpret")
    cold = sol_cold.impl_report(provenance=True)
    assert all("measured" not in e["sources"] for e in cold.values())


def test_mxu_matmul_elected_and_correct_on_pallas_backends():
    """ISSUE acceptance: the tiled Pallas matmul is the elected
    LINEAR/MATMUL flavour for MXU-aligned shapes on pallas_tpu (election)
    and pallas_interpret (election + execution parity at 1e-5, including a
    ragged-tail shape)."""
    for b, d_in, d_out in ((2, 128, 128), (3, 100, 65)):
        g, lin = _linear_graph(b, d_in, d_out)
        passes.elect_implementations(g, R.tpu_backend("TPU v5 lite"))
        assert lin.impl == "pallas.linear_mxu", (b, d_in, d_out)

        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(
            rng.standard_normal((d_out, d_in)), jnp.float32)}
        x = jnp.asarray(rng.standard_normal((b, d_in)), jnp.float32)
        ys = {}
        for bk in ("pallas_interpret", "xla"):
            g2, lin2 = _linear_graph(b, d_in, d_out)
            passes.elect_implementations(g2, get_backend(bk))
            ys[bk] = np.asarray(lower_graph(g2, get_backend(bk))(params, x))
        assert lin2.impl == "ref.linear"              # xla has no mxu
        np.testing.assert_allclose(ys["pallas_interpret"], ys["xla"],
                                   rtol=1e-5, atol=1e-5)


# -- calibration -----------------------------------------------------------------

def test_calibration_fit_recovers_coefficients():
    """Synthetic measurements generated from known coefficients are
    recovered by the non-negative least-squares fit."""
    from benchmarks.calibrate import fit
    a_true, b_true = 5e-12, 2e-10
    c = AutotuneCache()
    for m in (64, 128, 256, 512):
        flops = 2.0 * m ** 3
        nbytes = 3.0 * m * m * 4.0
        us = (a_true * flops + b_true * nbytes) * 1e6
        c.record("matmul", (m, m, m), "float32", "xla", "ref.matmul", us,
                 flops=flops, nbytes=nbytes)
    coeffs = fit(c)[("xla", "matmul")]
    assert coeffs["s_per_flop"] == pytest.approx(a_true, rel=1e-3)
    assert coeffs["s_per_byte"] == pytest.approx(b_true, rel=1e-3)
    assert coeffs["n"] == 4.0


def test_calibrated_cost_model_drives_cold_election():
    """Calibration coefficients apply when the exact op has no measurement:
    provenance flips from 'analytical' to 'calibrated'."""
    c = AutotuneCache()
    c.set_calibration("pallas_interpret", "linear",
                      {"s_per_flop": 1e-12, "s_per_byte": 1e-11, "n": 4.0})
    autotune.set_cache(c)
    g, lin = _linear_graph()
    passes.elect_implementations(g, get_backend("pallas_interpret"))
    assert lin.impl == "pallas.linear_mxu"            # same relative order
    assert g.election_provenance["pallas.linear_mxu"] == {"calibrated": 1}


# -- the autotune driver (tiny, through the dispatch table) ----------------------

def test_driver_measures_every_admissible_impl(tmp_path):
    """benchmarks.autotune times each dispatch-table candidate and records
    tuned configs plus calibration terms."""
    from benchmarks.autotune import tune
    cache = AutotuneCache()
    rows = tune("pallas_interpret", ("linear",), tiny=True,
                warmup=0, iters=1, cache=cache)
    names = {r[0] for r in rows}
    assert any("pallas.linear_mxu" in n for n in names)
    assert any("ref.linear" in n for n in names)
    got = cache.lookup("linear", (8, 64, 32), "float32", "pallas_interpret")
    assert got["pallas.linear_mxu"].config is not None   # tuned tile config
    assert got["pallas.linear_mxu"].flops > 0            # calibration terms


def test_driver_sweeps_registry_declared_tunables():
    """ISSUE acceptance: the sweep iterates whatever Tunable spaces the
    registry declares — attention blocks, DFP fusion sizing and the scan
    block all come back with a winning config, not just the matmul."""
    from benchmarks.autotune import tune
    cache = AutotuneCache()
    tune("pallas_interpret", ("attention", "fused", "rglru_scan"),
         tiny=True, warmup=0, iters=1, cache=cache)
    att = cache.lookup("attention", (1, 64, 2, 16), "float32",
                       "pallas_interpret")
    assert att["pallas.flash_attention"].config is not None
    fus = cache.lookup("fused", (64, 32), "float32", "pallas_interpret")
    assert fus["pallas.dfp_fused"].config is not None
    scan = cache.lookup("rglru_scan", (1, 16, 32), "float32",
                        "pallas_interpret")
    assert scan["pallas.rglru_scan"].config is not None


def test_measure_unpins_swept_config_when_impl_raises():
    """ISSUE satellite (regression): an impl raising mid-sweep must not
    leave the swept Tunable config pinned on the node — a stale pin would
    silently change what a later election or lowering executes.  Fails
    before the try/finally fix in core.measure.measure_impl_configs."""
    import types

    from repro.core.autotune import Tunable
    from repro.core.measure import measure_impl_configs

    _g, lin = _linear_graph()
    backend = get_backend("host_cpu")
    calls = []

    def exploding(node, vals, bk):
        calls.append(tuple(node.attrs.get("boom_block") or ()))
        if len(calls) >= 2:
            raise RuntimeError("kernel rejects this config")
        return vals[0]

    impl = types.SimpleNamespace(
        fn=exploding, tunable=Tunable("boom_block", lambda n, hw: []))

    with pytest.raises(RuntimeError):
        measure_impl_configs(lin, [jnp.ones((2, 16))], backend, impl,
                             [(8,), (16,), (32,)], warmup=0, iters=1)
    assert "boom_block" not in lin.attrs          # restored despite the raise
    assert calls == [(8,), (16,)]                 # raised on the second config

    # skip_errors=True keeps sweeping, reports the error per config, and
    # still restores the node
    calls.clear()
    out = measure_impl_configs(lin, [jnp.ones((2, 16))], backend, impl,
                               [(8,), (16,), (32,)], warmup=0, iters=1,
                               skip_errors=True)
    assert "boom_block" not in lin.attrs
    assert [m.error is None for m in out] == [True, False, False]
    assert all(m.us == float("inf") for m in out if m.error)


def test_sweep_node_restores_attrs_and_records_min_and_mean():
    """The real sweep leaves no pin behind and records both timing stats
    (us = min for elections, mean_us for figure-grade views)."""
    from repro.core.measure import sweep_node

    g, lin = _linear_graph(8, 64, 32)
    x = jnp.ones((8, 64), jnp.float32)
    w = jnp.ones((32, 64), jnp.float32)
    cache = AutotuneCache()
    out = sweep_node(lin, [x, w], get_backend("pallas_interpret"), cache,
                     warmup=0, iters=2)
    assert "mxu_block" not in lin.attrs
    got = cache.lookup("linear", (8, 64, 32), "float32", "pallas_interpret")
    for m in out:
        rec = got[m.impl]
        assert rec.mean_us >= rec.us > 0.0        # mean can never beat min
        assert rec.mean_us == m.mean_us


def test_time_call_is_min_of_individually_timed_iters(monkeypatch):
    """ISSUE satellite: election-grade timings use the min over iters (a
    hiccup inflates a mean but never a min); time_call_stats carries both."""
    from repro.core import measure

    ticks = iter([0.0, 30e-6, 1.0, 1.0 + 10e-6, 2.0, 2.0 + 20e-6])
    monkeypatch.setattr(measure.time, "perf_counter", lambda: next(ticks))
    t = measure.time_call_stats(lambda: 0, warmup=1, iters=3)
    assert t.min_us == pytest.approx(10.0)
    assert t.mean_us == pytest.approx(20.0)

    ticks = iter([0.0, 30e-6, 1.0, 1.0 + 10e-6, 2.0, 2.0 + 20e-6])
    assert measure.time_call(lambda: 0, warmup=1, iters=3) \
        == pytest.approx(10.0)


def test_verify_cache_roundtrip_with_attention_flip(tmp_path):
    """benchmarks.autotune --verify end to end: a tuned cache written to
    disk yields measured elections on reload, and the attention flip proof
    (cached block-size measurement flips the election, impl_report shows
    the pinned config) passes."""
    from benchmarks.autotune import tune, verify_cache
    path = str(tmp_path / "cache.json")
    cache = AutotuneCache()
    for ops in (("linear",), ("attention",)):
        tune("pallas_interpret", ops, tiny=True, warmup=0, iters=1,
             cache=cache)
    cache.save(path)
    assert verify_cache(path) == 0


# -- _EW_FLOPS calibration (perf_iter whole-model numbers) -----------------------

def test_ew_flops_fit_recovery():
    """ISSUE satellite: synthetic whole-model elementwise profiles generated
    from a known per-element cost are recovered by the fit, installing the
    fit changes the DFP cost terms, and degenerate data falls back to the
    nominal default."""
    k_true = 7.25
    samples = [(k_true * e, e) for e in (1e6, 4e6, 9e6)]
    assert passes.fit_ew_flops(samples) == pytest.approx(k_true)
    try:
        passes.calibrate_ew_flops(samples)
        assert passes.ew_flops() == pytest.approx(k_true)
        n = Node(OpKind.RELU, [ir.input_node((4, 8))], TensorSpec((4, 8)))
        flops, _streamed, _roundtrip = passes._node_cost_terms(n)
        assert flops == pytest.approx(k_true * 32)
    finally:
        passes.set_ew_flops(None)
    assert passes.ew_flops() == 5.0
    assert passes.fit_ew_flops([]) == 5.0
    assert passes.fit_ew_flops([(0.0, 0.0)]) == 5.0
