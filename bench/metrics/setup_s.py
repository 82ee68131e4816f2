"""Seconds from process start to the window's first instant: imports,
compile-cache loads, scan-width warming and the warm-up traffic."""


def read(ctx):
    return ctx.window.setup_s
