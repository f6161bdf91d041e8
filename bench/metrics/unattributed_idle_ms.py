"""Time the chip ran nothing outside every ``train.*`` span of the
driver loop, per round of the traced window (``bench/spans.py``): the
idle time no program span accounts for."""

from bench import spans


def read(ctx):
    return spans.idle_ms(ctx)
