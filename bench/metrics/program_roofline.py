"""The device program's share of its memory roofline: the least time the
answered reads need, each pattern's matching rows read once and the
result written once at the HBM peak, over the device's busy time."""


def read(ctx):
    if ctx.device is None or ctx.device.busy_s <= 0:
        return None
    peak = ctx.peaks[ctx.device_kind]["hbm_bytes_per_s"]
    moved = sum(ctx.least_bytes[r.text] for r in ctx.log
                if r.kind == "read" and r.ok and r.t_done <= ctx.t_close)
    if moved == 0:
        return None
    return 100.0 * (moved / peak) / ctx.device.busy_s
