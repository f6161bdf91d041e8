"""Time the chip ran nothing inside ``train.watchdog`` spans (the
divergence watchdog's check and host snapshot of the solver state), per
round of the traced window (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.idle_ms(ctx, "train.watchdog")
