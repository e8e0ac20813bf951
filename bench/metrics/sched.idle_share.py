"""Scheduler: share of the traced window in which the device was idle in
the self time of the scheduler's spans (``sol.step``, ``sol.admit``,
``sol.sample``, ``sol.prefill``, ``sol.decode``), in %."""
from harness.program import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["scheduler"]
