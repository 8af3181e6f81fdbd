"""Share of the traced window in which no operation ran on the device
(profiler trace, bench/xplane.py)."""


def read(ctx):
    if ctx.device is None:
        return None
    return 100.0 * ctx.device.idle_share
