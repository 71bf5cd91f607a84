"""Device program launches in the traced window per round completed."""


def read(ctx):
    rounds = ctx.sim.window_rounds
    return ctx.summary.launches / rounds if rounds else None
