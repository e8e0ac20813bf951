"""Mellum2's layers through SOL's normal path on the CPU: the routed
experts a layer holds (``nn.MoE``, ``OpKind.MOE``), RMSNorm, rotary
embeddings with the default and YaRN tables, and attention at a
``head_dim`` apart from ``d_model // n_heads``, each against a plain
formula written here."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.frontends import nn
from repro.frontends.extract import extract
from repro.frontends.optimize import optimize

BACKENDS = ("xla", "pallas_interpret")


def _sol(model, x, backend):
    return np.asarray(optimize(model, x.shape, backend=backend)(x))


@pytest.mark.parametrize("backend", BACKENDS)
def test_expert_shares_add_up_to_the_uncut_layer(backend):
    """Four chips' shares of a 64-expert top-8 layer (experts 0-15, 16-31,
    32-47, 48-63, each routing over all 64 and adding its own experts'
    part) sum to the whole layer."""
    d, f, e, k = 16, 8, 64, 8
    whole = nn.MoE(d, f, e, k)
    sd = whole.state_dict()
    x = np.random.default_rng(0).standard_normal((2, 5, d)).astype(np.float32)
    parts = []
    for first in (0, 16, 32, 48):
        share = nn.MoE(d, f, e, k, held=16, first=first)
        share.load_state_dict({
            "router": sd["router"],
            **{w: sd[w][first:first + 16]
               for w in ("w_gate", "w_up", "w_down")}})
        parts.append(_sol(share, x, backend))
    uncut = _sol(whole, x, backend)
    np.testing.assert_allclose(sum(parts), uncut, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(uncut, np.asarray(whole(x)), rtol=1e-5,
                               atol=1e-5)
    # each share's part is its own: none is the whole layer
    assert all(np.abs(p - uncut).max() > 1e-3 for p in parts)


def test_moe_node_carries_the_share():
    m = nn.MoE(16, 8, 64, 8, held=16, first=32)
    g = extract(m, (1, 3, 16))
    (node,) = [n for n in g.topo() if n.op.value == "moe"]
    assert node.attrs == {"top_k": 8, "first": 32, "norm_topk": True,
                          "score": "softmax"}
    assert node.inputs[1].spec.shape == (16, 64)
    assert node.inputs[2].spec.shape == (16, 16, 8)
    with pytest.raises(ValueError):
        nn.MoE(16, 8, 64, 8, held=16, first=56)


@pytest.mark.parametrize("backend", BACKENDS)
def test_rmsnorm_matches_the_formula(backend):
    m = nn.RMSNorm(24, eps=1e-6)
    g = np.random.default_rng(1).standard_normal(24).astype(np.float32)
    m.load_state_dict({"weight": g})
    x = np.random.default_rng(2).standard_normal((3, 24)).astype(np.float32)
    want = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                       + 1e-6) * g
    np.testing.assert_allclose(_sol(m, x, backend), want, rtol=1e-5,
                               atol=1e-5)


def _inv_freq_default(hd, theta):
    return np.array([theta ** (-2.0 * i / hd) for i in range(hd // 2)])


def _inv_freq_yarn(hd, theta, factor, orig, fast, slow):
    """YaRN's frequencies from its definition: a dimension whose
    wavelength 2π/ω turns more than ``fast`` times in the original context
    keeps ω, one that turns fewer than ``slow`` times takes ω / factor,
    with a linear ramp between the two (dimension indices, rounded out)."""
    def dim_of(turns):                # the dimension i that turns `turns`
        return hd * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo, hi = max(math.floor(dim_of(fast)), 0), \
        min(math.ceil(dim_of(slow)), hd - 1)
    out = []
    for i, w in enumerate(_inv_freq_default(hd, theta)):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append(w / factor * ramp + w * (1 - ramp))
    return np.array(out)


def test_rope_tables_match_their_definitions():
    """Mellum2's tables: the default frequencies on sliding layers, YaRN's
    (factor 16 over an original 8192 positions, β 32 and 1) on full
    layers, with the published attention factor."""
    hd, theta = 128, 500000.0
    np.testing.assert_allclose(nn.Rope.default(hd, theta).inv_freq,
                               _inv_freq_default(hd, theta), rtol=1e-6)
    yarn = nn.Rope.yarn(hd, theta, 16, 8192, 32, 1, 1.2772588722239782)
    want = _inv_freq_yarn(hd, theta, 16, 8192, 32, 1)
    np.testing.assert_allclose(yarn.inv_freq, want, rtol=1e-6)
    assert yarn.scale == 1.2772588722239782
    # high frequencies kept, low ones divided by the factor
    assert yarn.inv_freq[0] == 1.0
    assert yarn.inv_freq[-1] == pytest.approx(want[-1])
    assert want[-1] == pytest.approx(_inv_freq_default(hd, theta)[-1] / 16)


def _attention(x, wq, wk, wv, wo, h, kvh, hd, rope, window):
    """Causal GQA attention with rotary q and k, one sequence at a time,
    in float64."""
    b, s, _ = x.shape
    pos = np.arange(s)
    ang = pos[:, None] * np.asarray(rope.inv_freq, np.float64)[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1) * rope.scale
    sin = np.concatenate([np.sin(ang)] * 2, -1) * rope.scale

    def rot(t):                                   # (s, heads, hd)
        half = np.concatenate([-t[..., hd // 2:], t[..., :hd // 2]], -1)
        return t * cos[:, None] + half * sin[:, None]
    out = np.zeros((b, s, wo.shape[1]))
    for i in range(b):
        xi = x[i].astype(np.float64)
        q = rot((xi @ wq).reshape(s, h, hd))
        k = rot((xi @ wk).reshape(s, kvh, hd))
        v = (xi @ wv).reshape(s, kvh, hd)
        o = np.zeros((s, h, hd))
        for j in range(h):
            g = j // (h // kvh)
            sc = q[:, j] @ k[:, g].T / math.sqrt(hd)
            mask = pos[None] <= pos[:, None]
            if window:
                mask &= pos[:, None] - pos[None] < window
            sc = np.where(mask, sc, -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            o[:, j] = (p / p.sum(-1, keepdims=True)) @ v[:, g]
        out[i] = o.reshape(s, h * hd) @ wo
    return out


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", ["default", "yarn"])
def test_attention_at_its_own_head_dim_with_rope(backend, kind):
    """Four query heads of 16 over two KV heads on a width of 24 (24 / 4 =
    6 is not the head size), rotated, windowed for the default table."""
    d, h, kvh, hd, s = 24, 4, 2, 16, 12
    rope = (nn.Rope.default(hd, 10000.0) if kind == "default"
            else nn.Rope.yarn(hd, 10000.0, 4, 8, 32, 1, 1.2))
    window = 5 if kind == "default" else 0
    m = nn.MultiHeadAttention(d, h, kvh, head_dim=hd, rope=rope,
                              window=window)
    p = {k: np.asarray(v) for k, v in m.state_dict().items()}
    assert p["wq"].shape == (d, h * hd) and p["wo"].shape == (h * hd, d)
    x = np.random.default_rng(3).standard_normal((2, s, d)).astype(np.float32)
    want = _attention(x, p["wq"], p["wk"], p["wv"], p["wo"], h, kvh, hd,
                      rope, window)
    np.testing.assert_allclose(_sol(m, x, backend), want, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(m(jnp.asarray(x))), want,
                               rtol=1e-4, atol=1e-4)


def test_zero_rows_take_no_expert_s_work():
    """A bucket's padding rows are zeros: routed to no expert, so they make
    no pairs, touch no expert and come out as zeros, in both candidates."""
    from repro.kernels.moe import ref
    from repro.kernels.moe.ops import moe
    rng = np.random.default_rng(4)
    x = rng.standard_normal((6, 16)).astype(np.float32)
    x[3:] = 0.0
    w = [rng.standard_normal(s).astype(np.float32)
         for s in ((16, 64), (16, 16, 8), (16, 16, 8), (16, 8, 16))]
    experts, _ = ref.route(jnp.asarray(x), w[0], top_k=8)
    assert (np.asarray(experts)[3:] == -1).all()
    pairs, _ = np.asarray(ref.held_counts(experts, 0, 16))
    assert pairs == np.isin(np.asarray(experts)[:3], np.arange(16)).sum()
    for y in (ref.moe_ref(x, *w, top_k=8),
              moe(x, *w, top_k=8, tm=8, interpret=True)):
        np.testing.assert_array_equal(np.asarray(y)[3:], 0.0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(
            ref.moe_dense(x, *w, top_k=8)), rtol=1e-5, atol=1e-5)
