"""Staging: bytes of the bucket programs' outputs in the window (logits
and new KV rows, which the server brings to the host) per output token."""


def read(run):
    tokens = run.window_tokens()
    if not run.named("bench.forward") or not tokens:
        return None
    return run.window.counters["out_bytes"] / tokens
