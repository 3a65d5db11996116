"""Device time of the collectives (all-gathers, all-reduces,
reduce-scatters, all-to-alls) inside the decode steps, per step, averaged
over the cell's chips. Only a cell on several chips has any."""
from lib import readers

COLLECTIVE = r"^%(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"


def read(ctx):
    if ctx.run.kind != "lm" or ctx.chips < 2:
        return None
    n = readers.calls_in_window(ctx.trace, "bench.decode")
    devs = readers.used_devices(ctx.trace, ctx.chips)
    total = 0.0
    for d in devs:
        steps = readers.step_modules(ctx.trace, d, n)
        if not steps:
            return None
        total += readers.seconds(readers.ops_matching(ctx.trace, d,
                                                      COLLECTIVE, steps))
    return 1e3 * total / len(devs) / n
