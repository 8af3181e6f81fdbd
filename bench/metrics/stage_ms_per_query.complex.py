"""Mean time per answered read in scan staging: the snapshot lock, the
scans staged under it and the constants' upload of the read's dispatch
(the program's `stage` span; lanes of a stacked dispatch each carry it)."""


def read(ctx):
    stages = [sum(s.duration_s for s in t.find("stage"))
              for t in ctx.traces
              if t.root.name == "query" and t.root.attrs.get("outcome") == "ok"
              and t.find("stage")]
    return 1e3 * sum(stages) / len(stages) if stages else None
