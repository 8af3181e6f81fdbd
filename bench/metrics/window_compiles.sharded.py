"""window_compiles, read in the held sharded check."""
from harness import read_metric


def read(ctx):
    return read_metric("window_compiles", ctx)
