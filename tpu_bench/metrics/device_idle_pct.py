"""Share of the traced window in which no operation ran on the chip:
1 − (union of the device's op intervals) / window."""


def read(ctx):
    red = ctx.red
    if not red.devices or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
