"""Model FLOPs of the tokens trained in the window (the forward and
backward passes of every local step, ``bench.counts``; recomputation not
counted) over the window's length times the chips' bf16 peak, in %."""


def read(ctx):
    sim = ctx.sim
    if ctx.peak is None or ctx.window_s <= 0 or not sim.window_rounds:
        return None
    flops = sim.train_flops_per_token() * sim.window_tokens()
    return 100.0 * flops / (ctx.window_s * sim.chips
                            * ctx.peak["bf16_flops_per_s"])
