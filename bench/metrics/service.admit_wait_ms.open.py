"""p95 of submit - due time (ms) over the arrivals due in the window: the
wait for admission at a wave boundary, and how late the load generator
ran."""
from bench.window import percentile


def read(ctx):
    if not ctx.window.open_loop:
        return None
    return percentile(ctx.window.admit_waits_ms(), 95)
