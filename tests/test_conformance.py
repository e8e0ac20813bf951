"""Cross-backend conformance matrix (the 'Mind the Gap' lesson: backends
that silently diverge numerically are worse than backends that fail).

For every OpKind with a kernel family, the matrix runs **every impl the
dispatch table admits** — per backend (incl. ``host_cpu`` and
``pallas_interpret``) × dtype (f32/bf16) — against the family's ``ref.py``
oracle, under the single documented tolerance table below.  Impls that
declare a ``Tunable`` are additionally run at **every config in their tune
space**: a tuned config is a pure perf knob and must never change numerics.

CI runs this file standalone with ``--junitxml`` so the matrix ships as an
artifact next to the BENCH/cache series.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import get_backend
from repro.backends import registry as R
from repro.core import ir
from repro.core.ir import Node, OpKind, TensorSpec

BACKENDS = ("xla", "host_cpu", "pallas_interpret")
DTYPES = ("float32", "bfloat16")

# The documented per-(op, dtype) tolerance table: (rtol, atol) applied to
# every impl and every tuned config of that op.  f32 pins kernels to the
# oracle at 1e-5 (1e-4 for the recurrences, whose long dependency chains
# reorder summation); bf16 bounds follow the ~3 decimal digits the format
# carries, with extra headroom for the state-matrix accumulation in rwkv6.
TOLERANCE = {
    "linear":     {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "matmul":     {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "attention":  {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "attention_tp_shard": {"float32": (1e-5, 1e-5),
                           "bfloat16": (3e-2, 3e-2)},
    "decode_attention": {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "rglru_scan": {"float32": (1e-4, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "rwkv6_scan": {"float32": (1e-4, 1e-5), "bfloat16": (5e-2, 5e-2)},
    "fused":      {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "avgpool":    {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "conv2d":     {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
    "moe":        {"float32": (1e-5, 1e-5), "bfloat16": (3e-2, 3e-2)},
}

_RNG = np.random.default_rng(0)


def _arr(shape, dtype, scale=1.0):
    return jnp.asarray(_RNG.standard_normal(shape) * scale).astype(dtype)


def _case_linear(dtype):
    from repro.kernels.matmul.ref import matmul_ref
    x, w = _arr((4, 32), dtype), _arr((16, 32), dtype)   # w stored (out, in)
    node = Node(OpKind.LINEAR,
                [ir.input_node((4, 32), dtype),
                 ir.param_node((16, 32), dtype, name="w")],
                TensorSpec((4, 16), dtype), attrs={"out_features": 16})
    return node, [x, w], matmul_ref(x, w.T)


def _case_matmul(dtype):
    from repro.kernels.matmul.ref import matmul_ref
    x, w = _arr((12, 40), dtype), _arr((40, 24), dtype)
    node = Node(OpKind.MATMUL,
                [ir.input_node((12, 40), dtype),
                 ir.input_node((40, 24), dtype)],
                TensorSpec((12, 24), dtype))
    return node, [x, w], matmul_ref(x, w)


def _case_attention(dtype):
    from repro.kernels.flash_attention.ref import flash_attention_ref
    b, s, h, hd = 1, 64, 2, 16
    q, k, v = (_arr((b, s, h, hd), dtype) for _ in range(3))
    node = Node(OpKind.ATTENTION,
                [ir.input_node((b, s, h, hd), dtype) for _ in range(3)],
                TensorSpec((b, s, h, hd), dtype), attrs={"causal": True})
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    return node, [q, k, v], ref


def _case_attention_tp_shard(dtype):
    """The PER-SHARD attention problem a ``model=2`` mesh shard executes
    for the ``_case_attention`` family: heads are split across the model
    axis, so each shard runs the same kernel on h=1 of the 2-head global
    problem (see distributed/sharding.py).  Keeping this in the matrix pins
    every attention impl on the head-local shapes the sharded serving path
    actually dispatches — which sit in different autotune buckets than the
    global shapes."""
    from repro.kernels.flash_attention.ref import flash_attention_ref
    b, s, h, hd = 1, 64, 1, 16            # h = 2 heads / model axis of 2
    q, k, v = (_arr((b, s, h, hd), dtype) for _ in range(3))
    node = Node(OpKind.ATTENTION,
                [ir.input_node((b, s, h, hd), dtype) for _ in range(3)],
                TensorSpec((b, s, h, hd), dtype), attrs={"causal": True})
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    return node, [q, k, v], ref


def _case_decode_attention(dtype):
    """One query token vs a ragged KV cache; the oracle is pinned to the
    full causal re-forward path by the cross-check in the family's ref.py
    (and by tests/test_serving.py's decode-vs-reforward parity)."""
    from repro.kernels.decode_attention.ref import decode_attention_ref
    b, s, h, kv, hd = 3, 24, 4, 2, 16
    q = _arr((b, 1, h, hd), dtype)
    k, v = _arr((b, s, kv, hd), dtype), _arr((b, s, kv, hd), dtype)
    k_new, v_new = _arr((b, 1, kv, hd), dtype), _arr((b, 1, kv, hd), dtype)
    lens = jnp.asarray([0, 7, s], jnp.int32)      # empty / ragged / full
    node = Node(OpKind.DECODE_ATTENTION,
                [ir.input_node((b, 1, h, hd), dtype),
                 ir.input_node((b, s, kv, hd), dtype),
                 ir.input_node((b, s, kv, hd), dtype),
                 ir.input_node((b, 1, kv, hd), dtype),
                 ir.input_node((b, 1, kv, hd), dtype),
                 ir.input_node((b,), "int32")],
                TensorSpec((b, 1, h, hd), dtype))
    ref = decode_attention_ref(q[:, 0], k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), k_new[:, 0],
                               v_new[:, 0], lens)[:, None]
    return node, [q, k, v, k_new, v_new, lens], ref


def _case_rglru_scan(dtype):
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    b, t, d = 2, 24, 32
    a = jax.nn.sigmoid(_arr((b, t, d), "float32")).astype(dtype)
    bb, h0 = _arr((b, t, d), dtype, 0.1), _arr((b, d), dtype, 0.1)
    node = Node(OpKind.RGLRU_SCAN,
                [ir.input_node((b, t, d), dtype),
                 ir.input_node((b, t, d), dtype),
                 ir.input_node((b, d), dtype)],
                TensorSpec((b, t, d), dtype))
    return node, [a, bb, h0], rglru_scan_ref(a, bb, h0)[0]


def _case_rwkv6_scan(dtype):
    from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
    b, t, h, hd = 1, 16, 2, 8
    r, k, v = (_arr((b, t, h, hd), dtype, 0.5) for _ in range(3))
    logw = (-jnp.exp(_arr((b, t, h, hd), "float32", 0.5))).astype(dtype)
    u = _arr((h, hd), dtype, 0.3)
    s0 = jnp.zeros((b, h, hd, hd), dtype)
    node = Node(OpKind.RWKV6_SCAN,
                [ir.input_node((b, t, h, hd), dtype) for _ in range(4)]
                + [ir.input_node((h, hd), dtype),
                   ir.input_node((b, h, hd, hd), dtype)],
                TensorSpec((b, t, h, hd), dtype))
    return node, [r, k, v, logw, u, s0], rwkv6_scan_ref(r, k, v, logw,
                                                        u, s0)[0]


def _case_fused(dtype):
    from repro.kernels.dfp_fused.program import encode_program
    from repro.kernels.dfp_fused.ref import dfp_fused_ref
    rows, d = 24, 32
    spec = TensorSpec((rows, d), dtype)
    x = ir.input_node((rows, d), dtype, name="x")
    bias = ir.param_node((d,), dtype, name="bias")
    gain = ir.param_node((d,), dtype, name="gain")
    g = Node(OpKind.GELU, [x], spec)
    ba = Node(OpKind.BIAS_ADD, [g, bias], spec)
    a = Node(OpKind.ADD, [ba, x], spec)
    rn = Node(OpKind.RMSNORM, [a, gain], spec)
    node = Node(OpKind.FUSED, [x, bias, gain], spec, attrs={"length": 4},
                name="fused[gelu+bias+add+rmsnorm]", body=[g, ba, a, rn])
    vals = [_arr((rows, d), dtype), _arr((d,), dtype, 0.1),
            (jnp.ones((d,)) * 1.1).astype(dtype)]
    env = {id(i): v for i, v in zip(node.inputs, vals)}
    prog, operands = encode_program(node, env)
    ref = dfp_fused_ref(prog, operands, (rows, d), dtype)
    return node, vals, ref


def _case_avgpool(dtype):
    from repro.kernels.avgpool.ref import avgpool_ref
    x = _arr((1, 4, 12, 12), dtype)
    node = Node(OpKind.AVGPOOL, [ir.input_node((1, 4, 12, 12), dtype)],
                TensorSpec((1, 4, 10, 10), dtype),
                attrs={"kernel": 3, "stride": 1})
    return node, [x], avgpool_ref(x, 3, 3)


def _case_conv2d(dtype):
    x, w = _arr((1, 3, 8, 8), dtype), _arr((4, 3, 3, 3), dtype)
    node = Node(OpKind.CONV2D,
                [ir.input_node((1, 3, 8, 8), dtype),
                 ir.param_node((4, 3, 3, 3), dtype, name="w")],
                TensorSpec((1, 4, 8, 8), dtype),
                attrs={"stride": 1, "padding": 1, "out_channels": 4,
                       "groups": 1})
    ref = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return node, [x, w], ref


def _case_moe(dtype):
    """24 rows routed top-2 over 8 experts, experts 2..5 held here: one
    held expert is never among a row's best (its router column is the
    most negative for the positive rows), so it gets no rows, and pairs
    routed to experts that are not held add nothing."""
    from repro.kernels.moe.ref import held_counts, moe_dense, route
    t, d, f, e, held, first = 24, 32, 48, 8, 4, 2
    x = jnp.abs(_arr((t, d), "float32")).astype(dtype)
    router = _arr((d, e), "float32")
    router = router.at[:, first + 3].set(-jnp.abs(router).sum(1))
    ws = [_arr((held, d, f), dtype, 0.2), _arr((held, d, f), dtype, 0.2),
          _arr((held, f, d), dtype, 0.2)]
    attrs = {"top_k": 2, "first": first}
    _, touched = held_counts(route(x, router, top_k=2)[0], first, held)
    assert int(touched) < held
    node = Node(OpKind.MOE,
                [ir.input_node((t, d), dtype), ir.input_node((d, e)),
                 ir.input_node((held, d, f), dtype),
                 ir.input_node((held, d, f), dtype),
                 ir.input_node((held, f, d), dtype)],
                TensorSpec((t, d), dtype), attrs=attrs)
    return node, [x, router, *ws], moe_dense(x, router, *ws, **attrs)


CASES = {
    "linear": _case_linear,
    "matmul": _case_matmul,
    "attention": _case_attention,
    "attention_tp_shard": _case_attention_tp_shard,
    "decode_attention": _case_decode_attention,
    "rglru_scan": _case_rglru_scan,
    "rwkv6_scan": _case_rwkv6_scan,
    "fused": _case_fused,
    "avgpool": _case_avgpool,
    "conv2d": _case_conv2d,
    "moe": _case_moe,
}


@pytest.mark.parametrize("backend_name", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("op", sorted(CASES))
def test_conformance(op, dtype, backend_name):
    """Every admissible impl of (op, backend, dtype) — and every tuned
    config in its declared tune space — matches the family's ref.py oracle
    under the TOLERANCE table."""
    backend = get_backend(backend_name)
    node, vals, ref = CASES[op](dtype)
    cands = R.candidates(backend, node)
    assert cands, f"dispatch table admits nothing for {op} on {backend_name}"
    rtol, atol = TOLERANCE[op][dtype]
    ref32 = np.asarray(ref, np.float32)
    ran = 0
    for impl in cands:
        configs = [None]
        if impl.tunable is not None:
            space = impl.tunable.tune_space(node, backend.hw)
            if space:
                configs = space
        for cfg in configs:
            if impl.tunable is not None:
                impl.tunable.bind_config(node, cfg)
            out = impl.fn(node, list(vals), backend)
            assert out.dtype == jnp.dtype(dtype), (impl.name, out.dtype)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), ref32, rtol=rtol, atol=atol,
                err_msg=f"{impl.name} cfg={cfg} on {backend_name}/{dtype}")
            ran += 1
        if impl.tunable is not None:
            impl.tunable.bind_config(node, None)
    assert ran >= len(cands)


def test_matrix_covers_every_kernel_family():
    """The matrix must not silently drop an OpKind that has a registered
    non-reference impl — extending the dispatch table forces a conformance
    entry (or an explicit exemption here)."""
    R._load_entry_points()
    case_kinds = {
        "linear": OpKind.LINEAR, "matmul": OpKind.MATMUL,
        "attention": OpKind.ATTENTION,
        "attention_tp_shard": OpKind.ATTENTION,
        "decode_attention": OpKind.DECODE_ATTENTION,
        "rglru_scan": OpKind.RGLRU_SCAN,
        "rwkv6_scan": OpKind.RWKV6_SCAN, "fused": OpKind.FUSED,
        "avgpool": OpKind.AVGPOOL, "conv2d": OpKind.CONV2D,
        "moe": OpKind.MOE,
    }
    assert set(case_kinds) == set(CASES)
    have = {op for (_b, op) in R._BACKEND_IMPLS} | set(R._SHARED_IMPLS)
    missing = have - set(case_kinds.values())
    assert not missing, f"kernel families without a conformance case: {missing}"


# ---------------------------------------------------------------------------
# backward matrix: every registered gradient impl vs jax.vjp of the family's
# ref.py oracle (the same pullback ``executor.reference_vjp_grad`` serves as
# the capability fallback), every tuned config, f32 + bf16
# ---------------------------------------------------------------------------

# Backward tolerances get headroom over the forward table: a pullback chains
# the forward's reductions twice (recompute + transpose), so f32 kernels are
# pinned at 1e-4 (1e-3 for the recurrences, whose reverse scans re-associate
# the whole sequence) and bf16 at the format's ~3 digits with the same
# recurrence allowance.
GRAD_TOLERANCE = {
    "linear":     {"float32": (1e-4, 1e-4), "bfloat16": (5e-2, 5e-2)},
    "matmul":     {"float32": (1e-4, 1e-4), "bfloat16": (5e-2, 5e-2)},
    "attention":  {"float32": (1e-4, 1e-4), "bfloat16": (5e-2, 5e-2)},
    "attention_tp_shard": {"float32": (1e-4, 1e-4),
                           "bfloat16": (5e-2, 5e-2)},
    "rglru_scan": {"float32": (1e-3, 1e-4), "bfloat16": (5e-2, 5e-2)},
    "rwkv6_scan": {"float32": (1e-3, 1e-4), "bfloat16": (1e-1, 1e-1)},
    "fused":      {"float32": (1e-4, 1e-4), "bfloat16": (5e-2, 5e-2)},
    "avgpool":    {"float32": (1e-4, 1e-4), "bfloat16": (5e-2, 5e-2)},
}

# decode_attention and conv2d carry only the reference-vjp fallback, which
# IS the oracle — testing it against itself would be vacuous, so they are
# exempt here (the coverage guard below only demands cases for families
# with a non-reference backward).
GRAD_CASES = {op: CASES[op] for op in GRAD_TOLERANCE}


def _grad_oracle(node, vals, backend, ct):
    """``jax.vjp`` of the family's reference forward — per-input cotangents,
    None for non-inexact inputs.  Shared with the executor's capability
    fallback so the oracle and the fallback can never drift."""
    from repro.core.executor import reference_vjp_grad
    out = R._REFERENCE_IMPLS[node.op].fn(node, list(vals), backend)
    return out, reference_vjp_grad(node, (tuple(vals), out), ct, backend)


@pytest.mark.parametrize("backend_name", BACKENDS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("op", sorted(GRAD_CASES))
def test_grad_conformance(op, dtype, backend_name):
    """Every admissible *backward* impl of (op, backend, dtype) — and every
    config in its declared tune space — produces the same per-input
    cotangents as ``jax.vjp`` of the family's ref.py oracle, under the
    GRAD_TOLERANCE table."""
    backend = get_backend(backend_name)
    node, vals, _ref = GRAD_CASES[op](dtype)
    cands = R.grad_candidates(backend, node)
    if not cands or all(c.tier == R.TIER_REFERENCE for c in cands):
        pytest.skip(f"no non-reference backward for {op} on {backend_name}")
    rtol, atol = GRAD_TOLERANCE[op][dtype]
    ct = _arr(node.spec.shape, dtype)
    out, oracle = _grad_oracle(node, vals, backend, ct)
    res = (tuple(vals), out)
    ran = 0
    for impl in cands:
        configs = [None]
        if impl.tunable is not None:
            space = impl.tunable.tune_space(node, backend.hw)
            if space:
                configs = space
        for cfg in configs:
            if impl.tunable is not None:
                impl.tunable.bind_config(node, cfg)
            grads = impl.fn(node, res, ct, backend)
            assert len(grads) == len(vals), (impl.name, len(grads))
            for i, (g, o) in enumerate(zip(grads, oracle)):
                if o is None:
                    continue
                assert g is not None, \
                    f"{impl.name} dropped the input-{i} cotangent"
                np.testing.assert_allclose(
                    np.asarray(g, np.float32), np.asarray(o, np.float32),
                    rtol=rtol, atol=atol,
                    err_msg=f"{impl.name} d(input {i}) cfg={cfg} on "
                            f"{backend_name}/{dtype}")
            ran += 1
        if impl.tunable is not None:
            impl.tunable.bind_config(node, None)
    assert ran >= len(cands)


def test_grad_matrix_covers_every_backward_family():
    """Registering a non-reference backward impl forces a GRAD_CASES entry
    (or an explicit exemption here) — the backward matrix must not silently
    drop a family, mirroring the forward coverage guard."""
    R._load_entry_points()
    case_kinds = {
        "linear": OpKind.LINEAR, "matmul": OpKind.MATMUL,
        "attention": OpKind.ATTENTION,
        "attention_tp_shard": OpKind.ATTENTION,
        "rglru_scan": OpKind.RGLRU_SCAN, "rwkv6_scan": OpKind.RWKV6_SCAN,
        "fused": OpKind.FUSED, "avgpool": OpKind.AVGPOOL,
    }
    assert set(case_kinds) == set(GRAD_CASES)
    have = ({op for (_b, op) in R._GRAD_BACKEND_IMPLS}
            | set(R._GRAD_SHARED_IMPLS))
    missing = have - set(case_kinds.values())
    assert not missing, f"backward families without a grad case: {missing}"
