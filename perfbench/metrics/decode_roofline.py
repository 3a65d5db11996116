"""The decode step's share of its roofline: for each step, the least time
the chip could take, the larger of the bytes it must move over the HBM
bandwidth and its FLOPs over the bf16 peak, summed, over the steps' device
time. The bytes are the weights one device holds (the embedding table
less all but its rows) and the keys and values up to the position the
step reached, read, plus the new ones written: not the cache up to its
allocated length. The FLOPs are split evenly over the chips."""
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
    cfg, b = ctx.run.config, ctx.run.traffic["batch"]
    weights = ctx.run.held_weight_bytes()
    bw, peak = ctx.peak["hbm_bytes_per_s"], ctx.peak["bf16_flops_per_s"]
    least = sum(max((weights + counts.kv_bytes(cfg, b, p, shards=ctx.chips))
                    / bw,
                    counts.decode_flops(cfg, b, p) / ctx.chips / peak)
                for p in pos)
    return 100.0 * least / t
