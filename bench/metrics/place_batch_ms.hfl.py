"""Host milliseconds per round in the span ``hfl.place_batch`` (stacking
the pods' batches and placing them on the pods), from the program's
recorder over the window's rounds."""


def read(ctx):
    from repro import obs
    runs = obs.runs(ctx.sim.round_seeds)
    return None if runs is None else runs.per_round_ms("hfl.place_batch")
