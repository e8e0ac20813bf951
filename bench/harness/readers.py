"""What a per-layer metric's reader (``bench/metrics/<name>.py``) is given.

A reader is ``read(run: RunData) -> float | None``.  It returns ``None``
when it finds nothing to read (a wrapped call that no longer happens, a
kernel that is no longer on the path), and the harness then leaves the
metric out of the result line.  A share of a roofline or a peak is never
returned as 0 for want of data.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class RunData:
    cell: object              # harness.cell.Cell (config, mix, lm)
    window: object            # harness.cell.Window
    spans: List[Tuple]        # (name, start, end, meta), host clock, window
    setup: Dict[str, float]   # set-up phase times
    records: Optional[Dict]   # trace records (harness.trace.load)
    summary: Optional[Dict]   # harness.trace.reduce of them
    peaks: Optional[Dict]     # the chip's peaks

    @property
    def lm(self) -> Dict:
        return self.cell.lm

    @property
    def arch(self):
        """The configuration's model module (``bench/models/<name>.py``)."""
        return self.cell.arch

    def named(self, name: str) -> List[Tuple]:
        return [s for s in self.spans if s[0] == name]

    def inside(self, outer: Tuple, name: str) -> List[Tuple]:
        return [s for s in self.spans if s[0] == name
                and outer[1] <= s[1] and s[2] <= outer[2]]

    def window_tokens(self) -> int:
        w = self.window
        return sum(1 for x in w.records for t in x.tokens
                   if w.t0 <= t < w.t1)
