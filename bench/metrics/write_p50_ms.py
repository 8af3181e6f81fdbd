"""Median acknowledgement latency of the writes sent in the window."""
from harness import latencies_ms, median


def read(ctx):
    return median(latencies_ms(ctx, "write"))
