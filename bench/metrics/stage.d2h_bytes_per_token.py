"""Staging: bytes the program brought from the device to the host in the
window (the window's difference of ``SolServer.stats["d2h_bytes"]``: the
logits its fetch reads back) per output token."""


def read(run):
    tokens = run.window_tokens()
    d2h = run.window.counters.get("d2h_bytes")
    if d2h is None or not tokens:
        return None
    return d2h / tokens
