"""window_compiles, read in the held rw cell."""
from harness import read_metric


def read(ctx):
    return read_metric("window_compiles", ctx)
