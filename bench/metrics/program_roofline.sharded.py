"""program_roofline, read in the sharded check: the least bytes over
every chip's HBM peak, over the mean busy time of the chips."""
from harness import read_metric


def read(ctx):
    return read_metric("program_roofline", ctx)
