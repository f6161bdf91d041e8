"""Wall time of the window over the rounds completed in it (host clock,
driver loop included)."""


def read(ctx):
    return ctx.window_s / ctx.rounds * 1e3
