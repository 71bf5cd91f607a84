"""Device milliseconds per round of the tier-1 local-step program (the
jitted, vmapped ``train_step``), the mean over the devices."""

PROGRAM = r"jit_train_step"


def read(ctx):
    rounds = ctx.sim.window_rounds
    seconds = ctx.summary.seconds_of(PROGRAM)
    return 1e3 * seconds / rounds if rounds and seconds > 0 else None
