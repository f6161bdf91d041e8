"""Model FLOPs of the rounds in the traced window (``bench/flops.py``)
over the window and the bf16 peak of the chips that ran anything in it
(``reduction.chips``): the whole step's share of the peak, driver loop
included."""

from bench import flops


def read(ctx):
    red = ctx.reduction
    if red is None or red.busy_s <= 0:
        return None
    done = flops.flops_per_round(ctx.model, ctx.train) * ctx.rounds
    peak = red.chips * ctx.peaks["bf16_flops_per_s"]
    return done / ctx.window_s / peak * 100.0
