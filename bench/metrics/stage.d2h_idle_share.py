"""Staging: share of the traced window in which the device was idle while
the program brought a bucket program's outputs to the host
(``sol.fetch``), in %."""
from harness.program import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["fetch"]
