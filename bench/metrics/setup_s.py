"""Process start to the first line of the window: imports, both builds,
compilation or cache loads, the checked rounds and the window call's
first chunk."""


def read(ctx):
    return ctx.setup_s
