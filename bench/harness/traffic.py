"""One generator for every traffic mix: a mix is data (``bench/traffic``).

The sizes and times of a mix come from its fixed ``layout_seed``, so every
``--seed`` sends the same requests: the same prompt and answer lengths in
the same order, at the same arrival times or with the same think times.
``--seed`` draws only the token ids.  A window holds some tens of requests,
and their order is part of the work: near capacity it decides which
prompts meet a full batch.

- ``loop: open``: arrivals on a schedule (``arrivals.kind`` ``poisson``, or
  ``gamma`` with a coefficient of variation ``cv``), whether or not earlier
  requests have finished.  ``lead_in_s`` seconds of the same arrivals come
  before the window.
- ``loop: closed``: ``clients`` callers, each sending its next request a
  think time (``think_s``) after its answer arrives.  Round ``r`` holds each
  client's ``r``-th request.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from .spec import SpecError, seed_words

ROUNDS = 256             # requests held ready per closed-loop client


@dataclasses.dataclass
class Request:
    """One request as the generator makes it.  ``due`` is its scheduled
    arrival (seconds after traffic starts) in an open loop, or is set when
    its client sends it in a closed loop."""
    index: int
    prompt: np.ndarray
    max_new: int
    think_s: float = 0.0
    client: int = -1
    due: Optional[float] = None


def draw_len(dist: Dict, rng: np.random.Generator) -> int:
    kind = dist["dist"]
    if kind == "lognormal":
        x = np.exp(rng.normal(np.log(dist["median"]), dist["sigma"]))
    elif kind == "uniform":
        x = rng.integers(dist["min"], dist["max"] + 1)
    elif kind == "fixed":
        x = dist["value"]
    else:
        raise SpecError(f"unknown length distribution {kind!r}")
    return int(np.clip(round(float(x)), dist.get("min", 1),
                       dist.get("max", 1 << 30)))


def draw_gap(arr: Dict, rng: np.random.Generator) -> float:
    kind, rate = arr["kind"], arr["rate_per_s"]
    if kind == "poisson":
        return float(rng.exponential(1.0 / rate))
    if kind == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        return float(rng.gamma(shape, 1.0 / (rate * shape)))
    raise SpecError(f"unknown arrival process {kind!r}")


def draw_think(think: Dict, rng: np.random.Generator) -> float:
    if think["dist"] == "exponential":
        return float(rng.exponential(think["mean"]))
    if think["dist"] == "fixed":
        return float(think["value"])
    raise SpecError(f"unknown think-time distribution {think['dist']!r}")


def _sizes(mix: Dict, rng: np.random.Generator):
    return draw_len(mix["prompt_len"], rng), draw_len(mix["output_len"], rng)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int
              ) -> List[Request]:
    """Every request due before the window closes, in order of ``due``."""
    base = np.random.default_rng(mix["layout_seed"])
    toks = np.random.default_rng(seed_words(seed, 4))
    lead = float(mix.get("lead_in_s", 0.0))
    t, out = 0.0, []
    while True:
        t += draw_gap(mix["arrivals"], base)
        if t >= lead + seconds:
            break
        plen, out_len = _sizes(mix, base)
        out.append(Request(len(out), _tokens(toks, plen, vocab), out_len,
                           due=t))
    return out


def closed_loop(mix: Dict, seed: int, vocab: int) -> List[List[Request]]:
    """Per client, its requests in the order it sends them."""
    base = np.random.default_rng(mix["layout_seed"])
    n = int(mix["clients"])
    toks = np.random.default_rng(seed_words(seed, 4))
    per_client: List[List[Request]] = [[] for _ in range(n)]
    index = 0
    for _ in range(ROUNDS):
        for c in range(n):
            plen, out_len = _sizes(mix, base)
            think = draw_think(mix["think_s"], base)
            per_client[c].append(Request(index, _tokens(toks, plen, vocab),
                                         out_len, think_s=think, client=c))
            index += 1
    return per_client


def max_total(mix: Dict) -> int:
    """The longest context (prompt and answer) the mix can produce."""
    return (mix["prompt_len"].get("max", mix["prompt_len"].get("value", 0))
            + mix["output_len"].get("max", mix["output_len"].get("value", 0)))


def min_prompt(mix: Dict) -> int:
    return mix["prompt_len"].get("min", mix["prompt_len"].get("value", 1))
