"""Device idle time inside one decode step of the serving engine: the
median, over the window's decode steps, of the time inside the step's
``serve.decode`` span (the decode call, the argmax, the sync and the
token's copy to the host) in which no operation ran on the device,
averaged over the cell's chips."""
import statistics

from lib import program


def read(ctx):
    if ctx.run.kind != "lm":
        return None
    spans = program.spans(ctx)
    steps = [(s, e) for name, _, s, e in spans or ()
             if name == "serve.decode"]
    if not steps:
        return None
    idle = program.Idle(ctx.trace, ctx.chips)
    return 1e-6 * statistics.median(idle(s, e) for s, e in steps)
