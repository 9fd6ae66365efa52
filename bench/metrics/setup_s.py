"""Seconds from process start to the first timed request: weights, engine
build, compilation or cache loads, and warming every shape (host clock)."""


def read(ctx):
    return ctx.setup_s
