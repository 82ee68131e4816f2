"""Configurations scanned in the window (searches x grid size, in
billions) over the device's busy seconds in the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["busy_s"]:
        return None
    searches = ctx.counts["requests"] - ctx.counts["dedup_hits"]
    return searches * ctx.grid_size / ctx.trace["busy_s"] / 1e9
