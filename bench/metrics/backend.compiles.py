"""XLA compiles, and loads from the persistent compilation cache, inside
the window (the ``backend.compiles`` counter, fed by a
``jax.monitoring`` listener whether or not tracing is on)."""
from bench.counters import window_counts


def read(ctx):
    got = window_counts(("backend.compiles",))
    return None if got is None else got["backend.compiles"]
