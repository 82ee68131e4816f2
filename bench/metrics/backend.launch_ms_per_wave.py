"""Host time (ms) in the jax backend's launch loops (``backend.launch``
spans: enqueueing a scan's span programs) per service wave."""
from bench.counters import window_counts
from bench.spans import total_ms


def read(ctx):
    if window_counts(("backend.launches",)) is None:
        return None            # a program without the launch span
    if ctx.obs_spans is None or not ctx.window.waves:
        return None
    return total_ms(ctx.obs_spans, ("backend.launch",)) / ctx.window.waves
