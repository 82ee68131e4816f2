"""Search programs the backend built inside the window (the program's
``backend.programs_built`` counter, which counts while tracing is on)."""


def read(ctx):
    return ctx.programs_built
