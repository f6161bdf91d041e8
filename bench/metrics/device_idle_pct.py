"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    red = ctx.reduction
    if red is None or red.busy_s <= 0:
        return None
    return (1.0 - red.busy_s / red.window_s) * 100.0
