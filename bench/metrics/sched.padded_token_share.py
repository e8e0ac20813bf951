"""Scheduler: padded positions over bucket positions of the bucket
programs the window ran, in %: prefill positions (batch x sequence bucket
against prompt tokens) plus decode rows (batch bucket against residents),
from the ``padded`` and ``real`` of the program's ``sol.prefill`` and
``sol.decode`` spans."""
from harness.program import window_spans


def read(run):
    spans = window_spans(run)
    runs = [s[4] for s in spans or ()
            if s[0] in ("sol.prefill", "sol.decode")]
    positions = sum(a["real"] + a["padded"] for a in runs)
    if not positions:
        return None
    return 100.0 * sum(a["padded"] for a in runs) / positions
