"""The decode step's share of the chips' bf16 matrix peak: the model FLOPs
of the window's decode steps, each at the position it wrote, over their
device time, times the chips, times the peak."""
from lib import counts, readers


def read(ctx):
    if ctx.run.kind != "lm":
        return None
    pos = ctx.run.decode_positions()
    n = readers.calls_in_window(ctx.trace, "bench.decode")
    if n != len(pos):
        return None
    devs = readers.used_devices(ctx.trace, ctx.chips)
    t = sum(readers.seconds(readers.step_modules(ctx.trace, d, n))
            for d in devs) / len(devs)
    if t <= 0:
        return None
    b = ctx.run.traffic["batch"]
    flops = sum(counts.decode_flops(ctx.run.config, b, p) for p in pos)
    return 100.0 * flops / (t * ctx.chips * ctx.peak["bf16_flops_per_s"])
