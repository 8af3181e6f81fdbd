"""95th percentile of the latency of every read sent in the window."""
from harness import latencies_ms, percentile


def read(ctx):
    return percentile(latencies_ms(ctx, "read"), 95)
