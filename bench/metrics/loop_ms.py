"""Per-round time of the traced window spent outside the round program:
the driver loop's evaluation, watchdog snapshot, dispatch and log."""

MODULE = "jit_run_chunk"


def read(ctx):
    sec = ctx.reduction.module_s.get(MODULE) if ctx.reduction else None
    if not sec:
        return None
    return (ctx.window_s - sec) / ctx.rounds * 1e3
