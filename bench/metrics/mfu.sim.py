"""Model FLOPs of the rounds in the window (real participants' forward and
backward passes, the evaluations) over the window's length times the
chip's bf16 peak, in %. The CNN's f32 matmuls run as bf16 passes at
default precision, so the bf16 peak is the one that bounds them."""


def read(ctx):
    if ctx.peak is None or ctx.window_s <= 0:
        return None
    flops = ctx.sim.model_flops()
    return 100.0 * flops / (ctx.window_s * ctx.peak["bf16_flops_per_s"]) \
        if flops > 0 else None
