"""device_idle_share, read in the held sharded check: busy time is a
mean over the cell's chips (bench/xplane.py)."""
from harness import read_metric


def read(ctx):
    return read_metric("device_idle_share.complex", ctx)
