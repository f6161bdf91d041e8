"""Per-round time of the traced window spent outside the round program:
the driver loop's evaluation, watchdog snapshot, dispatch and log.  The
round program's time is one chip's: summed over the chips that ran
anything, over their number."""

MODULE = "jit_run_chunk"


def read(ctx):
    red = ctx.reduction
    sec = red.module_s.get(MODULE) if red else None
    if not sec:
        return None
    return (ctx.window_s - sec / red.chips) / ctx.rounds * 1e3
