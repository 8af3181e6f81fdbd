"""Mean time an answered read waited in the micro-batcher's queue, from
its submit to its batch's start (the program's `queue_wait` span)."""


def read(ctx):
    waits = [sum(s.duration_s for s in t.find("queue_wait"))
             for t in ctx.traces
             if t.root.name == "query" and t.root.attrs.get("outcome") == "ok"
             and t.find("queue_wait")]
    return 1e3 * sum(waits) / len(waits) if waits else None
