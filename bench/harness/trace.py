"""From a profiler trace of the window to device busy time, idle gaps and
the device operations that took most time.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain
records: device operations (``/device:TPU:n`` planes, line ``XLA Ops``),
each with the scope path it was traced under, and the benchmark's host
spans (``bench.*`` annotations).  ``reduce`` and ``scope_seconds`` work on
those records alone, so they are tested on small recorded traces.

The scope path of an operation (``jit(..)/linear:pallas.matmul/..``: the
program puts each node's operations under ``<op>:<impl>``) is the
``tf_op`` stat of the event's metadata in the XSpace protobuf, which
``jax.profiler.ProfileData`` does not expose; ``_op_scopes`` reads it from
the file's bytes (the protobuf wire format, by field number of
``tsl/profiler/protobuf/xplane.proto``).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict
from typing import Collection, Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# a scope that names a key of a program argument: params['3.1.1.weight']
_ARGUMENT = re.compile(r"\['([^'\]]+)'\]")


def load(trace_dir: str) -> Dict:
    """Plain records of the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"ops": {device: [[name, start_ns, dur_ns, scope], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}``; ``scope`` is "" where the
    trace names none."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    with open(files[-1], "rb") as f:
        scopes = _op_scopes(f.read())
    ops: Dict[str, List] = {}
    spans: List = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = list(line.events)
                scope = scopes.get(plane.name, [])
                if len(scope) != len(events):
                    print(f"[trace] {plane.name}: {len(scope)} scopes for "
                          f"{len(events)} ops; scopes left out",
                          file=sys.stderr)
                    scope = [""] * len(events)
                ops[plane.name] = [[e.name, e.start_ns, e.duration_ns, sc]
                                   for e, sc in zip(events, scope)]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    return {"ops": ops, "spans": spans}


# -- the scope of each device operation, from the XSpace protobuf -------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """``(field number, value)`` of one message: an integer for a varint,
    ``(start, end)`` for a length-delimited field, None for fixed ones."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _tf_op(buf: bytes, stats: List[Tuple[int, int]], names: Dict[int, str]
           ) -> Optional[str]:
    """The ``tf_op`` value among an event metadata's XStat messages (a
    string, or a reference to the stat metadata that holds it)."""
    for span in stats:
        meta = value = None
        for f, v in _fields(buf, *span):
            if f == 1:
                meta = v
            elif f == 5:                     # str_value
                value = _text(buf, v)
            elif f == 7:                     # ref_value
                value = names.get(v, "")
        if names.get(meta) == "tf_op":
            return value
    return None


def _plane_scopes(buf: bytes, span: Tuple[int, int]
                  ) -> Tuple[str, Optional[List[str]]]:
    """A plane's name and, for a device plane, the scope of every event of
    its ``XLA Ops`` line in order."""
    name, events, stat_names, event_meta = "", [], {}, {}
    for f, v in _fields(buf, *span):
        if f == 2:                                       # XPlane.name
            name = _text(buf, v)
            if not name.startswith(DEVICE_PREFIX):
                return name, None
        elif f == 3:                                     # XPlane.lines
            line_name, evs = None, []
            for g, w in _fields(buf, *v):
                if g == 2:
                    line_name = _text(buf, w)
                elif g == 4 and line_name == OPS_LINE:   # XLine.events
                    evs.append(w)
            if line_name == OPS_LINE:
                events += evs
        elif f in (4, 5):            # event_metadata / stat_metadata maps
            key = value = None
            for g, w in _fields(buf, *v):
                key, value = (w, value) if g == 1 else (key, w)
            if value is None:
                continue
            if f == 5:
                stat_names[key] = next(
                    (_text(buf, w) for g, w in _fields(buf, *value)
                     if g == 2), "")
            else:
                event_meta[key] = [w for g, w in _fields(buf, *value)
                                   if g == 5]
    meta_scope: Dict[int, str] = {}
    scopes = []
    for start, _ in events:
        # XEvent.metadata_id is field 1, written first (and left out at 0)
        key, i = _varint(buf, start)
        meta = _varint(buf, i)[0] if key == 8 else 0
        if meta not in meta_scope:
            meta_scope[meta] = _tf_op(buf, event_meta.get(meta, []),
                                      stat_names) or ""
        scopes.append(meta_scope[meta])
    return name, scopes


def _op_scopes(buf: bytes) -> Dict[str, List[str]]:
    """``device plane -> scope of each XLA Ops event``, in the order the
    file holds them, which is the order ``ProfileData`` gives them in."""
    buf = memoryview(buf)
    out = {}
    for f, v in _fields(buf, 0, len(buf)):
        if f == 1:                                       # XSpace.planes
            name, scopes = _plane_scopes(buf, v)
            if scopes is not None:
                out[name] = scopes
    return out


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


def scope_seconds(records: Dict, ops: Sequence[str],
                  weights: Collection[str] = ()) -> float:
    """Device seconds, summed over the devices, of the operations that
    start in the traced window under the scope of a node of a kind in
    ``ops`` (a scope path component ``<op>:<impl>``, any implementation),
    or under the scope of one of ``weights`` (``<argument>['<name>']``,
    where XLA puts the layout copy of a program argument)."""
    w0, w1 = window_of(records)
    under = re.compile("(?:^|/)(?:%s):" % "|".join(map(re.escape, ops)))
    weights = set(weights)

    def counted(scope: str) -> bool:
        return bool(under.search(scope)) or any(
            w in weights for w in _ARGUMENT.findall(scope))
    return 1e-9 * sum(d for dev_ops in records["ops"].values()
                      for _, s, d, scope in dev_ops
                      if w0 <= s < w1 and counted(scope))


_ASYNC_COPIES = {"async-start", "async-update", "async-done", "copy-start",
                 "copy-done"}
_ARRAY = re.compile(r"[a-z]\w*\[(\d+(?:,\d+)*)\]")


def prefetch_seconds(records: Dict, matrices: Collection[Tuple[int, int]]
                     ) -> float:
    """Device seconds, summed over the devices, of the asynchronous copies
    that start in the traced window with no scope and move a matrix of one
    of the ``matrices`` shapes or a block of its rows: how XLA streams a
    weight into the dot that reads it, ahead of it, and it gives the copy
    no scope."""
    w0, w1 = window_of(records)

    def counted(name: str) -> bool:
        opcode, _, ty = base_name(name).partition(" ")
        m = _ARRAY.search(ty)
        if opcode not in _ASYNC_COPIES or not m:
            return False
        dims = tuple(int(x) for x in m.group(1).split(","))
        return len(dims) == 2 and any(dims[1] == c and dims[0] <= r
                                      for r, c in matrices)
    return 1e-9 * sum(d for dev_ops in records["ops"].values()
                      for name, s, d, scope in dev_ops
                      if w0 <= s < w1 and not scope and counted(name))


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
