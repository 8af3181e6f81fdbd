"""Mean duration of the compactions the operator thread ran in the
window (host clock around store.compact())."""


def read(ctx):
    if not ctx.compactions:
        return None
    return 1e3 * sum(d for _, d in ctx.compactions) / len(ctx.compactions)
