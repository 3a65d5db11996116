"""Share of the traced window in which the device sat idle while the
pricing platform finished its tasks on the host: inside a
``pricing.finalize`` span (the per-task moments of one launch bucket) or
a ``pricing.records`` span (the reads of each price to the host),
averaged over the cell's chips."""
from lib import program

FINISH = ("pricing.finalize", "pricing.records")


def read(ctx):
    if ctx.run.kind != "pricing":
        return None
    spans = program.spans(ctx)
    finish = program.union((s, e) for name, _, s, e in spans or ()
                           if name in FINISH)
    if not finish:
        return None
    idle = program.Idle(ctx.trace, ctx.chips)
    return 100.0 * sum(idle(s, e) for s, e in finish) / (idle.hi - idle.lo)
