"""Joint plans resolved inside the window, over the window's seconds."""


def read(ctx):
    return len(ctx.window.resolved) / ctx.window.seconds
