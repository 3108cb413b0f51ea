"""Share of the traced window in which no operation ran on the device, in %.

Layer: device.  Busy time is the union of the device's operation intervals
(`bench.trace`), averaged over the chips; moves ``updates_per_s``."""


def read(ctx):
    red = ctx.trace
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
