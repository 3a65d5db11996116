"""The elementwise peak of one chip's vector unit, measured.

Google publishes no peak for a TPU v5e's vector unit, only for its matrix
unit. The pricing kernel's roofline share needs one, so this kernel
measures it: every grid step keeps one tile of 32-bit counters in
registers and runs rounds of the Threefry-2x32 mix (add, shift, shift,
or, xor) on it, the operations that dominate the pricing kernel, with no
memory traffic but one small store at the end. The best rate over a few
tile heights, in 32-bit elementwise operations per second, is entered in
``peaks.json`` with its measurement.

    python3 perfbench/lib/vpu_peak.py     # on the chip; prints JSON
"""
from __future__ import annotations

import functools
import json
import sys
import time

LANES = 128
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
#: Operations per element and loop iteration: each round is an add, two
#: shifts, an or and a xor.
OPS_PER_ITER = 5 * len(_ROT)


def _kernel(o_ref, *, rows: int, iters: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    base = (pl.program_id(0) * rows * LANES).astype(jnp.uint32)
    x0 = (base + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0) * LANES
          + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 1))
    x1 = x0 ^ jnp.uint32(0x9E3779B9)

    def body(_, xs):
        a, b = xs
        for r in _ROT:
            a = a + b
            b = ((b << jnp.uint32(r)) | (b >> jnp.uint32(32 - r))) ^ a
        return a, b

    a, b = jax.lax.fori_loop(0, iters, body, (x0, x1))
    o_ref[...] = a ^ b


@functools.lru_cache(maxsize=None)
def _call(rows: int, iters: int, blocks: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(_kernel, rows=rows, iters=iters)
    return jax.jit(lambda: pl.pallas_call(
        kern, grid=(blocks,),
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks * rows, LANES), jnp.uint32),
        interpret=jax.default_backend() == "cpu",
    )())


def ops_of(rows: int, iters: int, blocks: int) -> int:
    """32-bit elementwise operations one launch performs (the loop only)."""
    return blocks * rows * LANES * iters * OPS_PER_ITER


def measure(rows_list=(8, 16, 32, 64, 128, 256), iters: int = 2048,
            elements: int = 1 << 25, repeats: int = 3) -> dict:
    """Best ops/s over ``rows_list``, each timed by the host clock around
    ``repeats`` launches after a compiling one; every launch covers
    ``elements`` counters."""
    best = {}
    for rows in rows_list:
        blocks = elements // (rows * LANES)
        fn = _call(rows, iters, blocks)
        fn().block_until_ready()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn().block_until_ready()
            times.append(time.perf_counter() - t0)
        best[rows] = ops_of(rows, iters, blocks) / min(times)
    return {"ops_per_s_by_rows": best, "ops_per_s": max(best.values())}


if __name__ == "__main__":
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("vpu_peak: no TPU found", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"device_kind": dev.device_kind, **measure()}))
