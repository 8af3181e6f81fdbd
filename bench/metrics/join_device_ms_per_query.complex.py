"""Device time under a join's plan-operator scope (`join<k>/...`) per
read answered in the window (bench/phases.py, given the engine's
op_scopes()); nothing where a module ran without scopes."""
from harness import answered_reads


def read(ctx):
    n = answered_reads(ctx)
    if ctx.phases is None or ctx.phases.join_s is None or not n:
        return None
    return 1e3 * ctx.phases.join_s / n
