"""Staging: host-to-device bytes that ``runtime.packed`` staged in the
window (its ``TRANSFER_STATS["bytes"]`` counter) per output token."""


def read(run):
    tokens = run.window_tokens()
    if not run.named("bench.stage") or not tokens:
        return None
    return run.window.counters["h2d_bytes"] / tokens
