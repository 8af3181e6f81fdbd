"""Share of the traced window in which the device is idle while the
batcher prepares, stages or launches a dispatch (bench/phases.py)."""


def read(ctx):
    if ctx.phases is None:
        return None
    return 100.0 * ctx.phases.idle_share("dispatch")
