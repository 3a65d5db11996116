"""Share of the pricing kernel's device time spent on the launches the
platform throws away: kernel time inside ``pricing.warm`` spans over all
kernel time in the window, the kernel's operations matched as
``mc_kernel_roofline`` matches them."""
from lib import program, readers

KERNEL = r"mc_moments.*custom-call"


def read(ctx):
    if ctx.run.kind != "pricing":
        return None
    spans = program.spans(ctx)
    warm = [(name, s, e) for name, _, s, e in spans or ()
            if name == "pricing.warm"]
    if not warm:
        return None
    devs = readers.used_devices(ctx.trace, ctx.chips)
    total = sum(readers.seconds(readers.ops_matching(ctx.trace, d, KERNEL))
                for d in devs)
    if total <= 0:
        return None
    inside = sum(readers.seconds(readers.ops_matching(ctx.trace, d, KERNEL,
                                                      warm))
                 for d in devs)
    return 100.0 * inside / total
