"""End-to-end metrics of a window, from the benchmark's own host-clock
records of every request (never from the server's summary)."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def tokens_in(w) -> List[float]:
    return [t for x in w.records for t in x.tokens if w.t0 <= t < w.t1]


def output_tokens_per_s(w) -> float:
    """All output tokens produced in the window over its length."""
    return len(tokens_in(w)) / (w.t1 - w.t0)


def due_in(w) -> List:
    return [x for x in w.records if w.t0 <= x.due < w.t1]


def ttfts_s(w) -> List[float]:
    """Time to first token of every request due in the window, from its
    scheduled arrival; one with no first token by the window's end enters
    at its wait so far."""
    out = []
    for x in due_in(w):
        first = x.tokens[0] if x.tokens and x.tokens[0] < w.t1 else w.t1
        out.append(first - x.due)
    return out


def itls_s(w) -> List[float]:
    """Every gap between consecutive output tokens of one request that
    ends in the window, and the open gap of each request still in flight
    when the window closes."""
    out = []
    for x in w.records:
        ts = x.tokens
        out += [b - a for a, b in zip(ts, ts[1:]) if w.t0 <= b < w.t1]
        before = [t for t in ts if t < w.t1]
        finished_before = x.srv.done and len(before) == len(ts)
        if before and not finished_before:
            out.append(w.t1 - before[-1])
    return out


def pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else float("nan")


def end_to_end(w, setup_s: float) -> Dict[str, float]:
    return {"output_tokens_per_s": output_tokens_per_s(w),
            "ttft_p90_ms": 1e3 * pct(ttfts_s(w), 90),
            "itl_p95_ms": 1e3 * pct(itls_s(w), 95),
            "setup_s": setup_s}
