"""Model FLOPs of the rounds in the traced window (``bench/flops.py``)
over the window and the chip's bf16 peak: the whole step's share of
the peak, driver loop included."""

from bench import flops


def read(ctx):
    if ctx.reduction is None or ctx.reduction.busy_s <= 0:
        return None
    done = flops.flops_per_round(ctx.model, ctx.train) * ctx.rounds
    return done / ctx.window_s / ctx.peaks["bf16_flops_per_s"] * 100.0
