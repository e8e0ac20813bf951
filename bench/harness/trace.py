"""From a profiler trace of the window to device busy time, idle gaps and
the device operations that took most time.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain
records: device operations (``/device:TPU:n`` planes, line ``XLA Ops``)
and the benchmark's host spans (``bench.*`` annotations).  ``reduce``
works on those records alone, so it is tested on a small recorded trace.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(trace_dir: str) -> Dict:
    """Plain records of the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"ops": {device: [[name, start_ns, dur_ns, category], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    ops: Dict[str, List] = {}
    spans: List = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops[plane.name] = [
                    [e.name, e.start_ns, e.duration_ns,
                     dict(e.stats).get("hlo_category", "")]
                    for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    return {"ops": ops, "spans": spans}


def save(records: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(records, f)


def read(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window_of(records: Dict) -> Tuple[float, float]:
    """The traced window: the ``bench.window`` span."""
    for name, start, dur in records["spans"]:
        if name == WINDOW_SPAN:
            return float(start), float(start + dur)
    raise ValueError("no bench.window span in the trace")


class _Timeline:
    """Which ``bench.*`` spans are open at a time: the path of open spans,
    outermost first, or ``"no span"``; looked up by bisection."""

    def __init__(self, spans: List):
        ev = sorted(((s, d, n) for n, s, d in spans if n != WINDOW_SPAN),
                    key=lambda e: (e[0], -e[1]))      # outer spans first
        bounds = sorted({x for s, d, _ in ev for x in (s, s + d)})
        self.starts, self.paths = [], []
        opened: List[Tuple[float, str]] = []       # (end, name)
        i = 0
        for a, b in zip(bounds, bounds[1:]):
            while i < len(ev) and ev[i][0] <= a:
                opened.append((ev[i][0] + ev[i][1], ev[i][2]))
                i += 1
            opened = [(e, n) for e, n in opened if e > a]
            self.starts.append(a)
            self.paths.append("/".join(n for _, n in opened) or "no span")
        self.end = bounds[-1] if bounds else 0.0

    def at(self, t: float) -> str:
        k = bisect.bisect_right(self.starts, t) - 1
        if k < 0 or t >= self.end:
            return "no span"
        return self.paths[k]


def reduce(records: Dict, top: int = 10) -> Dict:
    """Busy and idle time of the window, averaged over the devices that
    ran anything, the longest idle gaps by the host span they fell in, and
    the device operations that took most time, by name and host span."""
    w0, w1 = window_of(records)
    where = _Timeline(records["spans"])
    busy, gaps = [], []
    by_op: Dict[str, float] = defaultdict(float)
    for dev, ops in records["ops"].items():
        iv = [(max(w0, s), min(w1, s + d)) for _, s, d, _ in ops
              if s + d > w0 and s < w1]
        if not iv:
            continue
        u = _union(iv)
        busy.append(sum(b - a for a, b in u))
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, where.at((a + b) / 2)))
        for name, s, d, _ in ops:
            if s >= w0 and s < w1:
                by_op[f"{base_name(name)} @ {where.at(s)}"] += d
    n_dev = max(1, len(busy))
    gap_by: Dict[str, float] = defaultdict(float)
    for g, path in gaps:
        gap_by[path] += g
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": len(busy),
        "device_ops": sorted(([k, v * 1e-9 / n_dev] for k, v in
                              by_op.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v * 1e-9 / n_dev] for k, v in
                             gap_by.items()), key=lambda kv: -kv[1])[:top],
        "longest_gap_s": max((g for g, _ in gaps), default=0.0) * 1e-9,
    }


def base_name(op: str) -> str:
    """A device operation without XLA's numbering: ``fusion.123`` ->
    ``fusion``; an HLO instruction as the TPU trace names it
    (``%fusion.39 = f32[4,8960]{1,0:T(4,128)} fusion(...)``) -> its opcode
    and result shape without layout (``fusion f32[4,8960]``)."""
    if " = " not in op:
        head, _, tail = op.rpartition(".")
        return head if head and tail.isdigit() else op
    rhs = op.split(" = ", 1)[1]
    if rhs.startswith("("):                 # a tuple-shaped result
        depth = 0
        for end, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        parts = re.sub(r"\{[^{}]*\}", "", rhs[1:end]).split(", ")
        ty = f"({parts[0]} x{len(parts)})"
        rest = rhs[end + 1:].lstrip()
    else:
        ty, _, rest = rhs.partition(" ")
        ty = re.sub(r"\{[^{}]*\}", "", ty)
    return f"{rest.split('(', 1)[0]} {ty}"
