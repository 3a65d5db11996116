"""Rate of the tensor-parallel exchange in the decode steps, in GB/s: the
bytes one chip receives in the all-gathers, as the program's
``serve.decode`` spans count them (``exchange_bytes``), summed over the
window's steps, over the device time of the collectives inside those
spans, averaged over the cell's chips. Read beside ``tp_collective_ms``:
a rate near the links' bandwidth says the collectives' time is bytes, a
low one that it is each collective's fixed cost. Nothing where the spans
carry no counter or the cell has one chip."""
from lib import readers

COLLECTIVE = r"^%(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"


def read(ctx):
    if ctx.run.kind != "lm" or ctx.chips < 2:
        return None
    from repro.obs.trace import default_tracer

    spans_in = getattr(default_tracer(), "spans_in", None)
    if spans_in is None:
        return None
    offset = ctx.trace.window()[0] - ctx.run.t0 * 1e9
    steps = [(name, s * 1e9 + offset, e * 1e9 + offset,
              args.get("exchange_bytes", 0))
             for name, _, s, e, args in spans_in(ctx.run.t0, ctx.run.t1,
                                                 args=True)
             if name == "serve.decode"]
    received = sum(b for *_, b in steps)
    if not received:
        return None
    inside = [(name, s, e) for name, s, e, _ in steps]
    devs = readers.used_devices(ctx.trace, ctx.chips)
    t = sum(readers.seconds(readers.ops_matching(ctx.trace, d, COLLECTIVE,
                                                 inside))
            for d in devs) / len(devs)
    if t <= 0:
        return None
    return 1e-9 * received / t
