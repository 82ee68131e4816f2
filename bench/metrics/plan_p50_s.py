"""Median submit -> resolve seconds of the queries the window counts;
in an open loop from each arrival's due time."""
from bench.window import percentile


def read(ctx):
    return percentile(ctx.window.latencies(), 50)
