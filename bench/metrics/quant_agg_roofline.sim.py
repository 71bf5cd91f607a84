"""The quantized server mean's least time on this chip over the device
time of the ``quant_agg`` kernel, in %. The least time counts the bytes
and operations the algorithm needs (``bench.counts.quant_agg_need``), not
what today's kernel moves, so a kernel that moves less reads higher and
never above 100%."""

from bench import counts

KERNEL = r"quant_agg"


def read(ctx):
    need = ctx.sim.quant_agg_need()
    seconds = ctx.summary.seconds_of(KERNEL, ctx.summary.op_s)
    if need is None or seconds <= 0 or ctx.peak is None:
        return None
    return 100.0 * counts.least_time_s(*need, ctx.peak) / seconds
