"""Process start to window start (host clock): imports, device check,
weights drawn, engine built, and the warm-up stream that compiles or
loads every program the window runs."""


def read(ctx):
    return ctx.setup_s
