"""The program's own spans (``repro.runtime.telemetry``) on the device
trace's clock, and the device's idle time cut by what the host was doing.

The program records its ``sol.*`` spans on the host clock
(``time.perf_counter``).  ``bench.step`` is on both clocks: in the
benchmark's own spans (``RunData.spans``, host clock) and in the trace
records (profiler ns).  Paired in order, the median of (trace start - host
start) maps program spans onto the device trace.  If the counts differ, or
the offsets' interquartile range is over ``MAX_SPREAD_S``, there is no
mapping and the idle readers leave their metrics out.

Idle time is cut by intersection, not by a gap's midpoint: each device's
idle intervals in the window (the complement of the union of its ``XLA
Ops``, as ``trace.reduce`` takes them) are cut by the innermost ``sol.*``
span open over each part, and averaged over the devices that ran anything.
Only spans inside a ``sol.step`` count; time outside every step is
``outside``.

Everything here reads the program while its server is alive, and returns
``None`` where the program has no such spans (an older program).
"""
from __future__ import annotations

import math
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from .trace import _union, window_of

MAX_SPREAD_S = 100e-6
STEP = "sol.step"

# the innermost span's name -> the part of the idle time it takes
CATEGORY = {
    "sol.gather": "staging", "sol.stage": "staging",
    "sol.stage.pack": "staging", "sol.stage.put": "staging",
    "sol.kv_write": "staging", "sol.arena.sync": "staging",
    "sol.fetch": "fetch",
    "sol.step": "scheduler", "sol.admit": "scheduler",
    "sol.sample": "scheduler", "sol.prefill": "scheduler",
    "sol.decode": "scheduler",
    "sol.forward": "dispatch", "sol.compile": "dispatch",
}
# the spans of a bucket forward -> its phase
FORWARDS = {"sol.prefill": "prefill", "sol.decode": "decode"}
PARTS = ("staging", "fetch", "scheduler", "dispatch", "other", "outside")

_memo: Dict[str, object] = {"run": None, "split": None}


def program_spans() -> Optional[List[Tuple]]:
    """Every span the program recorded, or None if it records none."""
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    return telemetry.spans()


def step_spans(spans: List[Tuple]) -> List[Tuple]:
    """The closed spans that lie inside a ``sol.step``, steps included."""
    keep = [False] * len(spans)
    for i, (name, _, t1, parent, _) in enumerate(spans):
        keep[i] = t1 is not None and (
            name == STEP or (parent is not None and keep[parent]))
    return [s for s, k in zip(spans, keep) if k]


def window_spans(run) -> Optional[List[Tuple]]:
    """The spans inside a ``sol.step`` that started in the window (host
    clock), or None if the program records none."""
    spans = program_spans()
    if spans is None:
        return None
    w = run.window
    return [s for s in step_spans(spans) if w.t0 <= s[1] < w.t1]


def window_forwards(run) -> Optional[List[Tuple[str, int, int]]]:
    """``(phase, batch, seq)`` of every bucket forward of the traced
    window: the program's ``sol.prefill`` and ``sol.decode`` spans (their
    ``bucket``, ``"<batch>x<seq>"``) that start in it, put on the trace's
    clock.  None without the program's spans or a clock offset."""
    spans = program_spans()
    if spans is None or not run.records:
        return None
    trace_steps = [s for s in run.records["spans"] if s[0] == "bench.step"]
    clock = clock_offset(run.named("bench.step"), trace_steps)
    if clock is None:
        return None
    w0, w1 = (x * 1e-9 - clock[0] for x in window_of(run.records))
    out = []
    for name, t0, _, _, attrs in step_spans(spans):
        if name in FORWARDS and w0 <= t0 < w1:
            batch, seq = attrs["bucket"].split("x")
            out.append((FORWARDS[name], int(batch), int(seq)))
    return out


def clock_offset(host_steps: List[Tuple], trace_steps: List
                 ) -> Optional[Tuple[float, float, float]]:
    """``(offset_s, spread_s, range_s)`` with trace seconds = host seconds + offset,
    from ``bench.step`` on the host clock (``(name, t0, t1, meta)``, s) and
    in the trace (``[name, start_ns, dur_ns]``), paired in order.  The
    spread is the offsets' interquartile range: one pair whose two
    timestamps another thread's turn at the interpreter pushed apart moves
    neither it nor the median.  None when the counts differ or the spread
    is over ``MAX_SPREAD_S``."""
    host = sorted(s[1] for s in host_steps)
    trace = sorted(s[1] * 1e-9 for s in trace_steps)
    if not host or len(host) != len(trace):
        return None
    offsets = [t - h for h, t in zip(host, trace)]
    q1, _, q3 = (statistics.quantiles(offsets, n=4) if len(offsets) > 1
                 else offsets * 3)
    if q3 - q1 > MAX_SPREAD_S:
        return None
    return statistics.median(offsets), q3 - q1, max(offsets) - min(offsets)


def innermost(spans: List[Tuple[str, float, float]]
              ) -> List[Tuple[float, float, str]]:
    """``(a, b, name)``: the innermost of nested spans ``(name, a, b)`` over
    each part of the time they cover, in order."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []          # (end, name), outer first
    t = -math.inf

    def close_until(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            out.append((t, end, name))
            t = end

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(a)
        if stack:
            out.append((t, a, stack[-1][1]))
        stack.append((b, name))
        t = a
    close_until(math.inf)
    return [(a, b, n) for a, b, n in out if b > a]


def split_idle(records: Dict, spans: List[Tuple], offset_s: float
               ) -> Optional[Dict[str, float]]:
    """Idle seconds of the traced window by ``PARTS``, averaged over the
    devices that ran anything; None when no device did.  ``spans`` are
    the program's, on the host clock."""
    w0, w1 = (x * 1e-9 for x in window_of(records))
    segs = innermost([(n, a + offset_s, b + offset_s)
                      for n, a, b, _, _ in step_spans(spans)])
    total = dict.fromkeys(PARTS, 0.0)
    n_dev = 0
    for ops in records["ops"].values():
        iv = [(max(w0, s * 1e-9), min(w1, (s + d) * 1e-9))
              for _, s, d, _ in ops if (s + d) * 1e-9 > w0 and s * 1e-9 < w1]
        if not iv:
            continue
        n_dev += 1
        edges = [w0] + [x for ab in _union(iv) for x in ab] + [w1]
        k = 0
        for a, b in zip(edges[::2], edges[1::2]):
            covered = 0.0
            while k < len(segs) and segs[k][1] <= a:
                k += 1
            j = k
            while j < len(segs) and segs[j][0] < b:
                part = min(b, segs[j][1]) - max(a, segs[j][0])
                total[CATEGORY.get(segs[j][2], "other")] += part
                covered += part
                j += 1
            total["outside"] += max(0.0, b - a) - covered
    if not n_dev:
        return None
    return {k: v / n_dev for k, v in total.items()}


def idle_shares(run) -> Optional[Dict[str, float]]:
    """``shares`` of the run's traced window, from the program's spans;
    computed once per run."""
    if _memo["run"] is not run:
        spans = program_spans()
        _memo["run"], _memo["split"] = run, (
            shares(run.records, run.named("bench.step"), spans)
            if spans is not None and run.records else None)
    return _memo["split"]


def shares(records: Dict, host_steps: List[Tuple], spans: List[Tuple]
           ) -> Optional[Dict[str, float]]:
    """The parts of the traced window's idle time by ``PARTS``, in % of the
    window, or None when the program's spans cannot be put on the trace's
    clock or no device ran anything.  Printed to stderr with the clock
    offset and the spans per step."""
    trace_steps = [s for s in records["spans"] if s[0] == "bench.step"]
    clock = clock_offset(host_steps, trace_steps)
    if clock is None:
        print(f"[program] no clock offset: {len(host_steps)} host and "
              f"{len(trace_steps)} traced bench.step spans, or offsets "
              f"spread over {MAX_SPREAD_S * 1e6:.0f} us", file=sys.stderr)
        return None
    offset, spread, span_range = clock
    idle = split_idle(records, spans, offset)
    if idle is None:
        return None
    w0, w1 = (x * 1e-9 - offset for x in window_of(records))
    inside = [s for s in step_spans(spans) if w0 <= s[1] < w1]
    steps = sum(1 for s in inside if s[0] == STEP)
    window_s = w1 - w0
    out = {k: 100.0 * v / window_s for k, v in idle.items()}
    print(f"[program] clock offset {offset!r} s over {len(trace_steps)} "
          f"bench.step pairs: interquartile range {spread * 1e6!r} us, "
          f"range {span_range * 1e6!r} us; "
          f"{len(inside) / max(1, steps)!r} spans per step", file=sys.stderr)
    print("[program] idle % of the window: " + ", ".join(
        f"{k} {v!r}" for k, v in out.items())
        + f"; sum {sum(out.values())!r}", file=sys.stderr)
    return out
