"""Staging: share of the traced window in which the device was idle while
the program's innermost span was staging work (``sol.gather``,
``sol.stage*``, ``sol.kv_write``, ``sol.arena.sync``), in %."""
from harness.program import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["staging"]
