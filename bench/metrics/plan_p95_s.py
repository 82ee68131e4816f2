"""95th percentile submit -> resolve seconds of the queries the window
counts; in an open loop from each arrival's due time, over every arrival
due in the window (those unresolved at its close are drained)."""
from bench.window import percentile


def read(ctx):
    return percentile(ctx.window.latencies(), 95)
