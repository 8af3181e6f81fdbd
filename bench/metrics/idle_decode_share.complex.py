"""Share of the traced window in which the device is idle while the
batcher waits and a decode worker transfers or decodes rows
(bench/phases.py)."""


def read(ctx):
    if ctx.phases is None:
        return None
    return 100.0 * ctx.phases.idle_share("decode")
