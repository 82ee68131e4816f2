"""Share (%) of the broker's requests in the window that needed a search:
100 * (requests - dedup and memo hits) / requests."""


def read(ctx):
    req = ctx.counts["requests"]
    if not req:
        return None
    return 100.0 * (req - ctx.counts["dedup_hits"]) / req
