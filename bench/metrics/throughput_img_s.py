"""Images answered inside the window, over the window's seconds (host
clock)."""


def read(ctx):
    return ctx.window.completed_in_window() / ctx.window.seconds
