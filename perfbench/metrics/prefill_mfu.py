"""The prefill step's share of the chips' bf16 matrix peak: the model
FLOPs of the window's prefills (from the configuration's shapes) over
their device time, times the chips, times the peak."""
from lib import counts, readers


def read(ctx):
    if ctx.run.kind != "lm":
        return None
    n = readers.calls_in_window(ctx.trace, "bench.prefill")
    devs = readers.used_devices(ctx.trace, ctx.chips)
    t = sum(readers.seconds(readers.step_modules(ctx.trace, d, n))
            for d in devs) / len(devs)
    if t <= 0:
        return None
    tr = ctx.run.traffic
    flops = n * counts.prefill_flops(ctx.run.config, tr["batch"],
                                     tr["prompt_len"])
    return 100.0 * flops / (t * ctx.chips * ctx.peak["bf16_flops_per_s"])
