"""Exposed collective time per read answered in the window: on each chip
the seconds in which a collective op runs and no other op does, as a
mean over the chips (bench/phases.py)."""
from harness import answered_reads


def read(ctx):
    n = answered_reads(ctx)
    if ctx.phases is None or ctx.phases.collective_exposed_s is None or not n:
        return None
    return 1e3 * ctx.phases.collective_exposed_s / n
