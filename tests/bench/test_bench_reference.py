"""The plain reference against the served model on the CPU at tiny widths,
and the control: the same comparison fails the reference computed one
precision down."""
import jax.numpy as jnp
import numpy as np
import pytest
from benchkit import REPO, TINY_LM

from harness import check, model, weights
from harness.spec import Spec

SPEC = Spec(REPO)
REF = SPEC.reference("pre_ln_gelu_lm")
ARCH = SPEC.model("pre_ln_gelu_lm")
MHA64 = dict(TINY_LM, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256,
             vocab=128)


class _Served:
    """A served request as the check reads it: the server's request and
    the logits row of each of its tokens."""

    def __init__(self, srv, logits):
        self.srv, self.logits = srv, logits


def _serve(lm, seed, prompts, gen, limits):
    """Serve ``prompts`` greedily; every request's logits at every served
    position, in order."""
    m = model.build_seeded(ARCH, lm, seed)
    srv = model.server(ARCH, lm, limits, m, weights.embedding(lm, seed),
                       "xla")
    reqs = [srv.submit(p, gen) for p in prompts]
    srv.warm_autotune()
    seen = {r.rid: [] for r in reqs}
    while srv.depth:
        for rid in srv.step():
            r = next(x for x in reqs if x.rid == rid)
            seen[rid].append(np.asarray(r.last_logits, np.float64))
    srv.close()
    return reqs, seen


@pytest.mark.parametrize("lm", [TINY_LM, MHA64], ids=["gqa", "mha_hd64"])
def test_served_prefill_and_decode_logits_match_the_reference(
        fast_autotune, lm):
    seed = 2**33 + 5
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, lm["vocab"], n, dtype=np.int32)
               for n in (5, 11, 3)]
    reqs, seen = _serve(lm, seed, prompts, 6,
                        {"max_seq": 32, "max_batch": 2, "slots": 3})
    params = weights.make_params(ARCH.weights(lm), seed)
    embed = weights.embedding(lm, seed)
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1])])
        ref = np.asarray(REF.logits(params, lm, embed[seq]), np.float64)
        pos = np.arange(len(r.prompt) - 1, len(seq))
        got = np.stack(seen[r.rid])
        assert got.shape == (6, lm["vocab"])
        scale = np.abs(ref[pos]).max()
        # float32 on the CPU: only the order of summation differs
        assert np.abs(got - ref[pos]).max() <= 1e-4 * scale
        assert (got.argmax(-1) == r.generated).all()
        # and the bfloat16 reference does not come that close
        low = np.asarray(REF.logits(params, lm, embed[seq],
                                    dtype=jnp.bfloat16), np.float64)
        assert np.abs(low[pos] - ref[pos]).max() > 10 * 1e-4 * scale


def test_control_fails_where_the_program_passes(fast_autotune):
    """The check's own numbers: the served tokens read a gap of 0 (no flip
    in float32 on the CPU), while the bfloat16 reference puts a token first
    that lies more than the limit below the reference's best."""
    lm = dict(TINY_LM, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
              vocab=2048)
    seed = 7
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, lm["vocab"], n, dtype=np.int32)
               for n in (20, 9, 14, 4)]
    reqs, seen = _serve(lm, seed, prompts, 24,
                        {"max_seq": 64, "max_batch": 4, "slots": 4})
    params = weights.make_params(ARCH.weights(lm), seed)
    got = check.compare(REF, lm, params, weights.embedding(lm, seed),
                        [_Served(r, seen[r.rid]) for r in reqs],
                        control=True)
    limits = {"logit_rel_mse": 1e-8, "tokens_min": 50}
    assert got["tokens"] == 96 and got["greedy_mismatches"] == 0
    assert got["gap_max"] == 0.0
    assert check.passed(check.verdict(got, 0, limits))
    assert got["control_logit_rel_mse"] > 100 * limits["logit_rel_mse"]
    assert got["control_gap_max"] > 0.0
    ctrl = dict(got, logit_rel_mse=got["control_logit_rel_mse"])
    assert not check.passed(check.verdict(ctrl, 0, limits))


def test_sample_holds_the_longest_and_follows_the_seed():
    class R:
        def __init__(self, rid, n):
            self.rid, self.generated = rid, [0] * n
    fin = [_Served(R(i, n), []) for i, n in enumerate([3, 9, 4, 9, 1, 5])]
    a = check.sample(fin, 3, 2**31 + 1)
    assert a[0].srv.rid == 1 and len(a) == 3
    assert [x.srv.rid for x in a] == [x.srv.rid for x in
                                      check.sample(fin, 3, 2**31 + 1)]
