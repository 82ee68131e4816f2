"""Scan program launches (the ``backend.launches`` counter: one per span
of grid rows the jax backend enqueues) per service wave."""
from bench.counters import window_counts


def read(ctx):
    got = window_counts(("backend.launches",))
    if got is None or not ctx.window.waves:
        return None
    return got["backend.launches"] / ctx.window.waves
