"""The model modules (``bench/models/<name>.py``): ``pre_ln_gelu_lm`` draws
the weights and counts the work that the harness drew and counted before
the architecture moved out of it, and the harness's generic pieces."""
import hashlib
import json

import numpy as np
import pytest
from benchkit import REPO, TINY_LM

from harness import weights
from harness.spec import Spec, lm_widths

SPEC = Spec(REPO)
ARCH = SPEC.model("pre_ln_gelu_lm")
CONFIGS = {c: lm_widths(json.loads((REPO / f"bench/configs/{c}.json")
                                   .read_text()))
           for c in ("qwen2-1.5b-widths", "gpt2-large")}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for k in sorted(arrays):
        h.update(k.encode())
        h.update(np.asarray(arrays[k]).tobytes())
    return h.hexdigest()


# sha256 of every weight (name, then bytes, in name order) and of the host
# embedding, as the harness drew them before the model module existed
@pytest.mark.parametrize("seed,params,embed", [
    (2**33 + 5,
     "addc26f5c7e0c456a422b98bbb266603333dca633987a63d17f6304a658cc5b9",
     "42a59a3bb0cf354aab4f7033c824d2018f565804ac2e7438af626bc41b817b40"),
    (7,
     "59f4f4b609baf3aade5f3b2bd83239081f6d06f55d1adaab4fe4f0cd93067745",
     "e9ae955c539998165ae8e98fc43177a56660f50ea87fbc63d2bf6d912ca219df")])
def test_pre_ln_weights_are_bit_identical(seed, params, embed):
    lm = lm_widths({"lm": TINY_LM})
    assert _digest(weights.make_params(ARCH.weights(lm), seed)) == params
    assert hashlib.sha256(weights.embedding(lm, seed).tobytes()) \
        .hexdigest() == embed


# prefill_flops at prompts 1, 65, 200, 448 and decode_flops at caches 0,
# 9, 300, 511, as the harness counted them before the move
@pytest.mark.parametrize("config,prefill,decode", [
    ("qwen2-1.5b-widths",
     [2316607488, 121065480192, 373862203392, 846429290496],
     [2316607488, 2318155776, 2368217088, 2404515840]),
    ("gpt2-large",
     [1544419840, 92536568320, 286949009920, 652845591040],
     [1544419840, 1546078720, 1599715840, 1638607360])])
def test_pre_ln_flops_are_the_same_integers(config, prefill, decode):
    lm = CONFIGS[config]
    assert [ARCH.prefill_flops(lm, p) for p in (1, 65, 200, 448)] == prefill
    assert [ARCH.decode_flops(lm, c) for c in (0, 9, 300, 511)] == decode


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_kernel_work_adds_up_to_the_model_s_projections(config):
    """A decode forward of one row: the ``linear`` and ``matmul`` nodes
    together do every projection, MLP and head operation of a decoded
    token, which is ``decode_flops`` without its attention; their bytes
    hold every projection weight once, and each node names the weight it
    reads.  A prefill bucket's rows scale the operations, and the head runs
    on every position."""
    lm = CONFIGS[config]
    d, h, v, n = lm["d_model"], lm["n_heads"], lm["vocab"], lm["n_layers"]
    work = [w for op in ("linear", "matmul")
            for w in ARCH.kernel_work(lm, op, "decode", 1, 512)]
    attn = n * 4.0 * h * (d // h) * 1
    assert sum(f for f, _, _ in work) == ARCH.decode_flops(lm, 0) - attn
    table = weights.shapes(ARCH.weights(lm))
    matrices = {k: s for k, s in table.items() if len(s) == 2}
    # every matrix read by exactly one node, and named as it is drawn
    assert sorted(w for _, _, w in work) == sorted(matrices)
    size = sum(int(np.prod(s)) for s in matrices.values())
    assert sum(b for _, b, _ in work) >= 4 * size
    assert sum(b for _, b, _ in work) < 4 * size * 1.01
    pre = [w for op in ("linear", "matmul")
           for w in ARCH.kernel_work(lm, op, "prefill", 2, 64)]
    assert sum(f for f, _, _ in pre) == 128 * sum(f for f, _, _ in work)
    assert ARCH.kernel_work(lm, "attention", "decode", 1, 512) == []


def test_head_dim_comes_from_the_configuration_where_given():
    """Mellum2-12B-A2.5B gives head_dim 128 where 2304 / 32 is 72."""
    mellum = {"d_model": 2304, "n_heads": 32, "head_dim": 128}
    assert lm_widths({"lm": mellum})["head_dim"] == 128
    assert lm_widths({"lm": {"d_model": 2304, "n_heads": 32}}
                     )["head_dim"] == 72
    assert CONFIGS["qwen2-1.5b-widths"]["head_dim"] == 128


def test_layer_kinds_by_period_and_leading_layers():
    assert weights.layer_kinds(7, ["sliding", "sliding", "full"],
                               ["dense"]) == {
        "dense": (0,), "sliding": (1, 2, 4, 5), "full": (3, 6)}
    assert weights.layer_kinds(3, ["x"]) == {"x": (0, 1, 2)}


def test_groups_of_layer_kinds_draw_apart():
    """Two groups of one entry on different layers and salts: each layer
    gets its own weight, and a group's draw does not depend on the other's
    layers."""
    g = weights.Group
    entry = (("{i}.w", (3,), "normal", 1.0),)
    a = weights.make_params((g(0, (0, 2), entry), g(100, (1,), entry)), 5)
    b = weights.make_params((g(0, (0, 2), entry),), 5)
    assert set(a) == {"0.w", "1.w", "2.w"}
    np.testing.assert_array_equal(a["0.w"], b["0.w"])
    np.testing.assert_array_equal(a["2.w"], b["2.w"])
    assert not np.array_equal(a["0.w"], a["1.w"])
