"""Self time (ms) of the DP driver's ``lockstep.queue`` and
``lockstep.consume`` spans, less the time in which spans nested inside
them ran (the broker's submit, flush and commit), per service wave."""
from bench.spans import self_ms


def read(ctx):
    if ctx.obs_spans is None or not ctx.window.waves:
        return None
    return self_ms(ctx.obs_spans, ("lockstep.queue", "lockstep.consume")) \
        / ctx.window.waves
