"""Reads served per device dispatch over the window: a stacked dispatch
serves several reads (server stats()["batched"] deltas), every other
answered read took one dispatch of its own."""


def read(ctx):
    a, b = ctx.stats0["batched"], ctx.stats1["batched"]
    stacked = b["stacked_dispatches"] - a["stacked_dispatches"]
    lanes = b["stacked_queries"] - a["stacked_queries"]
    n = sum(1 for r in ctx.log if r.kind == "read" and r.ok)
    dispatches = stacked + max(0, n - lanes)
    return n / dispatches if dispatches else None
