"""Share of scan-cache lookups over the window that found a staged
block of the current store version (store.scan_cache_stats())."""


def read(ctx):
    a, b = ctx.stats0["scan_cache"], ctx.stats1["scan_cache"]
    hits = b["hits"] - a["hits"]
    misses = b["misses"] - a["misses"]
    return 100.0 * hits / (hits + misses) if hits + misses else None
