"""Mean host time per answered read in the decode stage (the program's
`transfer` and `decode` spans)."""


def read(ctx):
    total, n = 0.0, 0
    for t in ctx.traces:
        spans = t.find("transfer") + t.find("decode")
        if spans:
            total += sum(s.duration_s for s in spans)
            n += 1
    return 1e3 * total / n if n else None
