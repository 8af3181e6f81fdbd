"""Seconds from process start to the window's opening: data generation,
store build, compiles or cache loads, and warm-up."""


def read(ctx):
    return ctx.setup_s
