"""Reads answered inside the window, over the window's length."""


def read(ctx):
    n = sum(1 for r in ctx.log if r.kind == "read" and r.ok
            and r.t_done <= ctx.t_close)
    return n / (ctx.t_close - ctx.t_open)
