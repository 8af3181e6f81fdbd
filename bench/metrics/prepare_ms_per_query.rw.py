"""Mean host time per read in parse and optimize (the program's `parse`
and `optimize` spans); a prepared-cache hit spends none."""


def read(ctx):
    reads = [t for t in ctx.traces if t.root.name == "query"]
    if not reads:
        return None
    total = sum(s.duration_s for t in reads
                for s in t.find("parse") + t.find("optimize"))
    return 1e3 * total / len(reads)
