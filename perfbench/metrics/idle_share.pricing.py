"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, in a pricing cell."""
from lib import readers


def read(ctx):
    if ctx.run.kind != "pricing":
        return None
    busy, window = readers.busy_window(ctx.trace, ctx.chips)
    return 100.0 * (1.0 - busy / window)
