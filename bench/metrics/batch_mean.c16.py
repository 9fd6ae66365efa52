"""Real (unpadded) images per dispatch in the window, from the batcher's
dispatch log."""


def read(ctx):
    n = sum(ctx.dispatches.values())
    if not n:
        return None
    return sum(b * k for b, k in ctx.dispatches.items()) / n
