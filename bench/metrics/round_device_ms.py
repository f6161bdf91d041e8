"""Device time of the round program (``jit_run_chunk``) in the traced
window, per round and per chip (summed over the chips that ran
anything, over their number)."""

MODULE = "jit_run_chunk"


def read(ctx):
    red = ctx.reduction
    sec = red.module_s.get(MODULE) if red else None
    return None if not sec else sec / red.chips / ctx.rounds * 1e3
