"""The whole pricing window's share of the chips' measured vector peak:
the operations its workloads need (each task's allocated paths once) over
the traced window's length times the chips times the peak. It bounds the
kernel's roofline share from below whatever the kernel is: discarded
launches, padding and host time all count against it."""
from lib import readers


def read(ctx):
    if ctx.run.kind != "pricing":
        return None
    _, window = readers.busy_window(ctx.trace, ctx.chips)
    return 100.0 * ctx.run.needed_ops() / (
        window * ctx.chips * ctx.peak["vpu_ops_per_s"])
