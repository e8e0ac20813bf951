"""The comparison that decides ``correct``.

After the window closes, a sample of the requests the server finished,
drawn from ``--seed`` with the longest among them, is run through the plain
reference: once per request, over its prompt and the tokens it was served
(teacher-forced).  Two numbers are compared: the mean, over every served
position, of the squared difference between the logits row the server
sampled from and the reference's row, relative to the reference's variance
there; and how many served tokens are not the first of the row they were
sampled from (greedy decoding: none).  The first served token of a request
comes from the prefill program, the others from the decode program after
its KV rows went through the host arena, so both are covered.  The widest
gap of a served token below the reference's best logit is reported beside
them.

The control puts the reference in the program's place at the next
precision down (bfloat16 for the configuration's float32) and reads the
same numbers at the same positions, its tokens being the ones bfloat16
puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np

from .spec import seed_words


def sample(finished: Sequence, k: int, seed: int) -> List:
    """``k`` finished requests (records with the server's request ``srv``)
    drawn from ``seed``; the one with the most served tokens is always
    among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda x: (len(x.srv.generated), -x.srv.rid))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(seed_words(seed, 5))
    pick = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=None)
def _stat_fns():
    import jax
    import jax.numpy as jnp

    def stats(ref, other, idx, tok):
        """Per position: the gap of ``tok`` below the reference's best, and
        the mean squared difference of ``other`` from the reference over the
        vocabulary, relative to the reference's variance there."""
        rows = ref[idx].astype(jnp.float32)
        got = jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]
        gap = rows.max(-1) - got
        rel = ((other.astype(jnp.float32) - rows) ** 2).mean(-1) \
            / rows.var(-1)
        return gap, rel

    @jax.jit
    def served(ref, rows, idx, tok):
        return stats(ref, rows, idx, tok)

    @jax.jit
    def control(ref, low, idx):
        first = low[idx].astype(jnp.float32).argmax(-1)
        return stats(ref, low[idx], idx, first)
    return served, control


def _positions(req) -> np.ndarray:
    """Positions whose logits chose the served tokens: the prompt's last
    position, then one per decoded token."""
    n = len(req.generated)
    return np.arange(len(req.prompt) - 1, len(req.prompt) - 1 + n)


def _padded(a: np.ndarray, mult: int = 128) -> np.ndarray:
    n = -(-len(a) // mult) * mult
    out = np.zeros((n,) + a.shape[1:], a.dtype)
    out[: len(a)] = a
    return out


def compare(reference, lm: Dict, params, embed: np.ndarray, records: Sequence,
            control: bool = False) -> Dict[str, float]:
    """Run the reference (and with ``control`` the bfloat16 control) over
    the sampled ``records`` (each with the server's request ``srv`` and the
    logits row of every served token) and return the readings.  The
    reference is given each sequence's embedded rows and, as ``ids``, its
    token ids, for a model whose embedding lives in its graph:

    - ``logit_rel_mse``: over every served position, the mean squared
      difference of the served logits from the reference's, relative to
      the reference's variance at that position; the number compared;
    - ``logit_rel_max``: the same at the worst position (reported);
    - ``gap_max``: the widest gap of a served token below the reference's
      best, in logits (reported, not compared: it does not separate the
      program from the control, see PERF.md);
    - ``greedy_mismatches``: served tokens that are not the first of the
      row they were sampled from (greedy decoding: exactly 0);
    - ``tokens``: served tokens compared;
    - with ``control``, the same three for the bfloat16 reference:
      ``control_logit_rel_mse``, ``control_logit_rel_max`` and
      ``control_gap_max``."""
    import jax.numpy as jnp
    served_fn, control_fn = _stat_fns()
    acc = {k: [] for k in ("gap", "rel", "cgap", "crel")}
    mismatches = 0
    for x in records:
        r = x.srv
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        rows = embed[seq]
        pos = _positions(r)
        n = len(pos)
        idx = jnp.asarray(_padded(pos.astype(np.int32)))
        tok = jnp.asarray(_padded(np.asarray(r.generated, np.int32)))
        served = np.stack(x.logits).astype(np.float32)
        mismatches += int((served.argmax(-1) != np.asarray(r.generated))
                          .sum())
        got = jnp.asarray(_padded(served))
        ref = reference.logits(params, lm, rows, ids=seq)
        gap, rel = served_fn(ref, got, idx, tok)
        acc["gap"].append(np.asarray(gap)[:n])
        acc["rel"].append(np.asarray(rel)[:n])
        if control:
            low = reference.logits(params, lm, rows, dtype=jnp.bfloat16,
                                   ids=seq)
            cgap, crel = control_fn(ref, low, idx)
            acc["cgap"].append(np.asarray(cgap)[:n])
            acc["crel"].append(np.asarray(crel)[:n])
            del low
        del ref, got

    def agg(key, fn):
        vals = np.concatenate(acc[key]) if acc[key] else np.array([np.inf])
        return float(fn(vals))
    out = {"tokens": int(sum(len(g) for g in acc["gap"])),
           "greedy_mismatches": mismatches,
           "logit_rel_mse": agg("rel", np.mean),
           "logit_rel_max": agg("rel", np.max),
           "gap_max": agg("gap", np.max)}
    if control:
        out["control_logit_rel_mse"] = agg("crel", np.mean)
        out["control_logit_rel_max"] = agg("crel", np.max)
        out["control_gap_max"] = agg("cgap", np.max)
    return out


def verdict(readings: Dict[str, float], compiles: int, limits: Dict
            ) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit."""
    return {
        "logit_rel_mse": {"value": readings["logit_rel_mse"],
                          "limit": limits["logit_rel_mse"]},
        "greedy_mismatches": {"value": readings["greedy_mismatches"],
                              "limit": 0},
        "tokens_checked": {"value": readings["tokens"],
                           "limit": limits["tokens_min"], "at_least": True},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    ok = True
    for c in checks.values():
        v, lim = c["value"], c["limit"]
        good = v >= lim if c.get("at_least") else v <= lim
        ok = ok and bool(np.isfinite(v)) and good
    return ok
