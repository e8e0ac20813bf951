"""Frontend tests: graph extraction fidelity, sol.optimize ==
framework-eager numerics (the paper's core correctness claim), offloading
modes, deployment artifacts."""
from _hypo import hypothesis, st  # real hypothesis, or skip-stubs when absent
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.frontends import deploy as D
from repro.frontends import nn
from repro.frontends.extract import extract
from repro.frontends.offload import device
from repro.frontends.optimize import optimize


@pytest.fixture(autouse=True)
def _native_mode():
    device.set("cpu", 0, mode="native")
    yield
    device.set("cpu", 0, mode="native")


def test_extract_mlp_structure():
    m = nn.mlp_8192(3, 64, 32, 10)
    g = extract(m, (2, 32))
    kinds = [n.op.value for n in g.topo() if n.op.value not in
             ("input", "param")]
    assert kinds.count("linear") == 3
    assert kinds.count("relu") == 2
    assert set(g.params) == {"0.weight", "0.bias", "2.weight", "2.bias",
                             "4.weight", "4.bias"}


@pytest.mark.parametrize("builder,shape", [
    (lambda: nn.mlp_8192(3, 64, 32, 10), (2, 32)),
    (nn.small_cnn, (2, 3, 16, 16)),
    (nn.depthwise_cnn, (2, 3, 16, 16)),
])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_sol_matches_framework(builder, shape, backend):
    model = builder()
    x = np.random.default_rng(0).standard_normal(shape).astype(np.float32)
    y_ref = np.asarray(model(jnp.asarray(x)))
    sol = optimize(model, shape, backend=backend)
    y_sol = np.asarray(sol(x))
    np.testing.assert_allclose(y_sol, y_ref, rtol=2e-4, atol=2e-4)


def test_parameter_update_invalidates_offload_context():
    """The paper's context caching: params are re-staged only on change."""
    model = nn.mlp_8192(2, 32, 16, 4)
    sol = optimize(model, (1, 16))
    x = np.ones((1, 16), np.float32)
    y1 = np.asarray(sol(x))
    sd = model.state_dict()
    sd["0.weight"] = sd["0.weight"] * 2.0
    sol.load_state_dict(sd)                    # framework-side update
    y2 = np.asarray(sol(x))
    assert not np.allclose(y1, y2), "stale offload context"


def test_transparent_offload_host_roundtrip():
    model = nn.mlp_8192(2, 32, 16, 4)
    sol = optimize(model, (2, 16))
    device.set("cpu", 0, mode="transparent")
    x = np.random.randn(2, 16).astype(np.float32)
    y = sol(x)
    assert isinstance(y, np.ndarray)           # host output, host input
    y_ref = np.asarray(model(jnp.asarray(x)))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind,index", [("tpu", 0), ("cpu", 3)])
def test_device_set_refuses_missing_device(kind, index):
    """A device the host does not have raises; it is never replaced by
    another device (the earlier state stays selected)."""
    before = device.state
    with pytest.raises(ValueError):
        device.set(kind, index)
    assert device.state == before


def test_deploy_roundtrip_and_independence():
    model = nn.small_cnn()
    sol = optimize(model, (1, 3, 16, 16))
    x = np.random.randn(1, 3, 16, 16).astype(np.float32)
    y_ref = np.asarray(sol(x))
    blob = D.deploy(sol, (1, 3, 16, 16))
    loaded = D.load(blob)
    y = np.asarray(loaded(jnp.asarray(x)))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_deployed_params_staged_exactly_once():
    """ISSUE 5 regression: DeployedModel must device-put its params ONCE at
    load (via runtime.packed.transfer), not re-upload host arrays on every
    call."""
    from repro.runtime import packed as P
    model = nn.mlp_8192(2, 32, 16, 4)
    sol = optimize(model, (1, 16))
    blob = D.deploy(sol, (1, 16))
    P.reset_transfer_stats()
    served = D.load(blob)
    assert served.staged_leaves == len(sol._params_for_call())
    after_load = dict(P.TRANSFER_STATS)
    assert after_load["packed_dmas"] + after_load["direct_dmas"] >= 1
    # staged buffers are device arrays, not host ndarrays
    leaves = jax.tree.leaves(served.params)
    assert leaves and all(isinstance(v, jax.Array) for v in leaves)
    x = jnp.ones((1, 16), jnp.float32)
    y1 = np.asarray(served(x))
    y2 = np.asarray(served(x))
    assert dict(P.TRANSFER_STATS) == after_load, \
        "params were re-staged after load"
    np.testing.assert_allclose(y1, y2)
    np.testing.assert_allclose(y1, np.asarray(sol(np.ones((1, 16),
                                                          np.float32))),
                               rtol=1e-5, atol=1e-5)


def test_export_fn_nested_pytree_roundtrip():
    """ISSUE 5 regression: the artifact format must round-trip NESTED dict
    params, not just the flat SolModel dict."""
    params = {
        "block": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                  "b": np.ones(3, np.float32)},
        "scale": np.float32(2.0),
    }

    def fn(p, x):
        return (x @ p["block"]["w"] + p["block"]["b"]) * p["scale"]

    blob = D.export_fn(fn, params,
                       jax.ShapeDtypeStruct((4, 2), jnp.float32))
    m = D.load(blob)
    assert set(m.params) == {"block", "scale"}
    assert set(m.params["block"]) == {"w", "b"}
    x = np.random.default_rng(0).standard_normal((4, 2)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(m(jnp.asarray(x))),
                               np.asarray(fn(params, x)),
                               rtol=1e-6, atol=1e-6)


def test_deployed_model_carries_election_metadata():
    model = nn.mlp_8192(2, 32, 16, 4)
    sol = optimize(model, (1, 16))
    loaded = D.load(D.deploy(sol, (1, 16)))
    assert loaded.impl_report() == sol.impl_report()
    assert loaded.impl_report(by_kind=True) == sol.impl_report(by_kind=True)
    live = sol.impl_report(provenance=True)
    dep = loaded.impl_report(provenance=True)
    assert {k: v["sources"] for k, v in dep.items()} \
        == {k: v["sources"] for k, v in live.items()}


_LAYER = st.sampled_from(["linear", "relu", "gelu", "ln"])


@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(layers=st.lists(_LAYER, min_size=1, max_size=6),
                  seed=st.integers(0, 1000))
def test_random_models_property(layers, seed):
    """Property: for random Sequential models, SOL's optimized executable is
    numerically identical to framework-eager execution."""
    rng = np.random.default_rng(seed)
    mods, d = [], 24
    for l in layers:
        if l == "linear":
            d2 = int(rng.integers(8, 40))
            mods.append(nn.Linear(d, d2))
            d = d2
        elif l == "relu":
            mods.append(nn.ReLU())
        elif l == "gelu":
            mods.append(nn.GELU())
        else:
            mods.append(nn.LayerNorm(d))
    model = nn.Sequential(*mods)
    x = rng.standard_normal((3, 24)).astype(np.float32)
    y_ref = np.asarray(model(jnp.asarray(x)))
    sol = optimize(model, (3, 24))
    np.testing.assert_allclose(np.asarray(sol(x)), y_ref,
                               rtol=1e-4, atol=1e-4)


def test_programming_effort_loc_table():
    """The paper's Table 'programming effort': our backends must stay small
    (≤3000 LOC/backend in the paper; ours are far smaller because DFP
    codegen is shared — assert the invariant holds)."""
    from pathlib import Path
    import repro
    root = Path(repro.__file__).parent
    be = sum(len(p.read_text().splitlines())
             for p in (root / "backends").glob("*.py"))
    assert be < 3000
    fe = sum(len(p.read_text().splitlines())
             for p in (root / "frontends").glob("*.py"))
    assert fe < 3000   # paper: ≤2400 per frontend
