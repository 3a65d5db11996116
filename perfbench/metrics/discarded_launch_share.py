"""Share of the chip platform's own wall clock (``platform_wall_s`` of
each execute) that its kept records do not account for: launches whose
results are thrown away, and host work around them."""


def read(ctx):
    if ctx.run.kind != "pricing" or not ctx.run.reports:
        return None
    kept = wall = 0.0
    for rep in ctx.run.reports:
        kept += sum(r.latency for r in rep.records)
        wall += sum(rep.platform_wall_s.values())
    return 100.0 * (1.0 - kept / wall)
