"""Device milliseconds per round of the tier-2 sync program (the jitted
cluster ``sync``), the mean over the devices. Nothing runs beside it, so
all of it is exposed collective time."""

PROGRAM = r"jit_sync\b"


def read(ctx):
    rounds = ctx.sim.window_rounds
    seconds = ctx.summary.seconds_of(PROGRAM)
    return 1e3 * seconds / rounds if rounds and seconds > 0 else None
