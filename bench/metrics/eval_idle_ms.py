"""Time the chip ran nothing inside ``train.eval`` spans (the driver
loop's mean loss of the consensus mean), per round of the traced window
(``bench/spans.py``).  The span's wall time is mostly the wait for the
round; this is the part of it in which the chip idled."""

from bench import spans


def read(ctx):
    return spans.idle_ms(ctx, "train.eval")
