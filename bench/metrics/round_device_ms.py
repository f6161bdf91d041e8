"""Device time of the round program (``jit_run_chunk``) in the traced
window, per round."""

MODULE = "jit_run_chunk"


def read(ctx):
    sec = ctx.reduction.module_s.get(MODULE) if ctx.reduction else None
    return None if not sec else sec / ctx.rounds * 1e3
