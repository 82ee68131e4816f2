"""Time (ms) of the float64 commit (``broker.wave.commit`` spans) per
service wave."""
from bench.spans import total_ms


def read(ctx):
    if ctx.obs_spans is None or not ctx.window.waves:
        return None
    return total_ms(ctx.obs_spans, ("broker.wave.commit",)) \
        / ctx.window.waves
