"""Device milliseconds of the local-SGD training programs per round."""

PROGRAMS = r"local_sgd"


def read(ctx):
    rounds = ctx.sim.window_rounds
    seconds = ctx.summary.seconds_of(PROGRAMS)
    return 1e3 * seconds / rounds if rounds and seconds > 0 else None
