"""Dispatch-table tests: per-op impl registration, the capability fallback
chain, the cost-based election pass, and host_cpu↔xla numerical parity —
the PR's 'a backend is a table of flavours, not executor edits' claim."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import (Backend, available_backends, get_backend,
                            register_backend, register_impl)
from repro.backends import registry as R
from repro.core import ir, passes
from repro.core.executor import lower_graph
from repro.core.ir import Graph, Node, OpKind, TensorSpec
from repro.frontends import nn
from repro.frontends.optimize import optimize


def _relu_graph():
    x = ir.input_node((2, 8), name="x")
    y = Node(OpKind.RELU, [x], x.spec)
    return Graph([x], [y], {}), y


# -- registration & fallback chain -------------------------------------------

def test_register_impl_overrides_fallback():
    """A tier-0 backend-specific impl beats the shared and reference tiers,
    and a later registration beats an earlier one."""
    bk = register_backend(dataclasses.replace(
        get_backend("xla"), name="test_override"))
    g, node = _relu_graph()
    assert bk.resolve(node).name == "ref.relu"

    marker = 7.5
    register_impl("test_override", OpKind.RELU,
                  lambda n, vals, backend: jnp.maximum(vals[0], 0.0) + marker,
                  name="test_override.relu_v1")
    assert bk.resolve(node).name == "test_override.relu_v1"
    y = lower_graph(g, bk)({}, jnp.array([[-1.0, 2.0] * 4] * 2))
    np.testing.assert_allclose(np.asarray(y)[0, 0], marker)   # -1 → 0 → +7.5

    register_impl("test_override", OpKind.RELU,
                  lambda n, vals, backend: jnp.maximum(vals[0], 0.0),
                  name="test_override.relu_v2")
    assert bk.resolve(node).name == "test_override.relu_v2"


def test_unregistered_op_falls_back_to_reference():
    """Ops without backend-specific or shared impls resolve to the reference
    tier on every backend — the chain never dead-ends."""
    for bk in [get_backend(name)
               for name in ("xla", "host_cpu", "pallas_interpret")] \
            + [R.tpu_backend("TPU v5 lite")]:
        _, node = _relu_graph()
        impl = bk.resolve(node)
        assert impl.tier == R.TIER_REFERENCE
        assert impl.name == "ref.relu"


def test_capability_gates_shared_impls():
    """The shared Pallas DFP kernel is admissible only for backends with the
    'pallas' capability; others compose (reference tier)."""
    body = [Node(OpKind.RELU, [], TensorSpec((4, 32)))]
    fused = Node(OpKind.FUSED, [ir.input_node((4, 32))], TensorSpec((4, 32)),
                 body=body)
    names = {b: [i.name for i in get_backend(b).candidates(fused)]
             for b in ("xla", "host_cpu", "pallas_interpret")}
    assert names["xla"] == ["ref.compose"]
    assert names["host_cpu"] == ["ref.compose"]
    assert names["pallas_interpret"] == ["pallas.dfp_fused", "ref.compose"]


def test_attention_reference_fallback_runs():
    """An op only the kernel subpackages know (no executor branch) lowers
    through its registered reference impl."""
    q = ir.input_node((2, 16, 4, 8), name="q")
    k = ir.input_node((2, 16, 4, 8), name="k")
    v = ir.input_node((2, 16, 4, 8), name="v")
    att = Node(OpKind.ATTENTION, [q, k, v], q.spec)
    g = Graph([q, k, v], [att], {})
    key = jax.random.PRNGKey(0)
    qa, ka, va = (jax.random.normal(kk, (2, 16, 4, 8))
                  for kk in jax.random.split(key, 3))
    y = lower_graph(g, get_backend("xla"))({}, qa, ka, va)
    assert np.asarray(y).shape == (2, 16, 4, 8)

    from repro.kernels.flash_attention.ref import flash_attention_ref
    ref = flash_attention_ref(
        qa.transpose(0, 2, 1, 3), ka.transpose(0, 2, 1, 3),
        va.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -- election pass ------------------------------------------------------------

def test_election_annotates_every_node():
    g, _ = _relu_graph()
    g = passes.run_pipeline(g, get_backend("xla"))
    for n in g.topo():
        if n.op not in (OpKind.INPUT, OpKind.PARAM, OpKind.OUTPUT):
            assert n.impl, f"{n} not elected"
    assert sum(g.elections.values()) == g.stats()["elected"]


def test_election_prefers_streamed_dfp_kernel():
    """On a pallas-capable backend the cost model elects the depth-first
    kernel for supported fusion groups (streamed beats roundtrip bytes)."""
    model = nn.mlp_8192(2, 32, 16, 4)
    sol_p = optimize(model, (2, 16), backend="pallas_interpret")
    sol_x = optimize(model, (2, 16), backend="xla")
    assert any(k == "pallas.dfp_fused" for k in sol_p.impl_report())
    assert all(not k.startswith("pallas.") for k in sol_x.impl_report())


def test_foreign_tier0_annotation_rejected():
    """A tier-0 impl is private to its backend: a stale annotation pointing
    at another backend's kernel must not leak across re-lowering."""
    from repro.core.executor import _impl_for
    x = ir.input_node((2, 16), name="x")
    w = ir.param_node((8, 16), name="w")
    lin = Node(OpKind.LINEAR, [x, w], TensorSpec((2, 8)),
               attrs={"out_features": 8})
    assert not R.get_impl("host_cpu.linear_oi").admissible(
        get_backend("xla"), lin)
    lin.impl = "host_cpu.linear_oi"        # elected on host_cpu earlier
    assert _impl_for(lin, get_backend("xla")).name == "ref.linear"
    assert _impl_for(lin, get_backend("host_cpu")).name == "host_cpu.linear_oi"


def test_stale_election_falls_back_on_other_backend():
    """A graph elected for one backend re-lowers correctly on another: the
    executor drops inadmissible annotations and walks the chain."""
    model = nn.mlp_8192(2, 32, 16, 4)
    x = np.random.default_rng(3).standard_normal((2, 16)).astype(np.float32)
    g_p = optimize(model, (2, 16), backend="pallas_interpret")
    y_p = np.asarray(g_p(x))
    # re-lower the pallas-elected graph with the xla backend
    fn = jax.jit(lower_graph(g_p.graph, get_backend("xla")))
    params = {k: jnp.asarray(model.state_dict()[k]) for k in g_p.graph.params}
    y_x = np.asarray(fn(params, jnp.asarray(x)))
    np.testing.assert_allclose(y_p, y_x, rtol=1e-5, atol=1e-5)


# -- host_cpu backend ----------------------------------------------------------

def test_host_cpu_registered_with_own_hw():
    assert "host_cpu" in available_backends()
    bk = get_backend("host_cpu")
    assert bk.hw.name == "host_cpu"
    assert bk.linear_weight_layout == "oi"
    assert bk.conv_layout == "nchw"
    assert "pallas" not in bk.capabilities


def test_host_cpu_elects_its_overrides():
    sol = optimize(nn.small_cnn(), (2, 3, 16, 16), backend="host_cpu")
    report = sol.impl_report()
    assert "host_cpu.linear_oi" in report
    assert "host_cpu.conv2d_nchw" in report
    # DFP groups fall back to the composed reference flavour (no pallas)
    assert "ref.compose" in report


@pytest.mark.parametrize("builder,shape", [
    (nn.small_cnn, (2, 3, 16, 16)),          # Conv + DFP chains + Linear
    (lambda: nn.mlp_8192(3, 64, 32, 10), (2, 32)),
    (nn.depthwise_cnn, (2, 3, 16, 16)),
])
def test_host_cpu_parity_vs_xla(builder, shape):
    """ISSUE acceptance: host_cpu output matches xla to atol 1e-5 on graphs
    mixing Linear, Conv and DFP fusion groups."""
    model = builder()
    x = np.random.default_rng(1).standard_normal(shape).astype(np.float32)
    ys = {}
    for bk in ("xla", "host_cpu"):
        ys[bk] = np.asarray(optimize(model, shape, backend=bk)(x))
    np.testing.assert_allclose(ys["host_cpu"], ys["xla"],
                               rtol=1e-5, atol=1e-5)


# -- hardware spec by device kind ----------------------------------------------

def test_pallas_tpu_spec_keyed_by_device_kind():
    """pallas_tpu takes its HardwareSpec from the device-kind table; an
    unknown kind (the CPU this suite runs on among them) is an error, not
    the v5e default."""
    assert R.tpu_backend("TPU v5 lite").hw is R.TPU_V5E
    assert not R.tpu_backend("TPU v5 lite").interpret
    with pytest.raises(ValueError, match="no HardwareSpec"):
        R.tpu_backend("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no HardwareSpec"):
        get_backend("pallas_tpu")          # this host's device kind is cpu


def test_pallas_interpret_refused_on_tpu(monkeypatch):
    """Interpret mode on a TPU host raises and names pallas_tpu."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="pallas_tpu"):
        get_backend("pallas_interpret")
