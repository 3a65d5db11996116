"""The pricing kernel's share of its roofline: the elementwise operations
its launches were asked for (active paths x steps x the count per
path-step in lib/counts.py; padding paths are waste, not work) over the
kernel's device time times the vector unit's measured peak. The kernel
holds its paths in registers and writes one tile per task, so it moves
almost no bytes: the compute bound is the roofline."""
from lib import readers

KERNEL = r"mc_moments.*custom-call"


def read(ctx):
    if ctx.run.kind != "pricing":
        return None
    devs = readers.used_devices(ctx.trace, ctx.chips)
    t = sum(readers.seconds(readers.ops_matching(ctx.trace, d, KERNEL))
            for d in devs) / len(devs)
    if t <= 0:
        return None
    return 100.0 * ctx.run.launched_ops() / (
        t * ctx.chips * ctx.peak["vpu_ops_per_s"])
