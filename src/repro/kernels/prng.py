"""Counter-based RNG shared by the Pallas kernels and the jnp oracles.

Threefry-2x32 (20 rounds) — the same generator JAX uses internally —
implemented with only uint32 add/xor/rotate so the identical code runs

  * inside a Pallas TPU kernel body (VPU integer ops), and
  * in the pure-jnp reference oracle,

which makes kernel-vs-oracle comparisons exact up to float summation
order. Counter-based generation is the right shape for Monte Carlo on a
systolic/SIMD machine: the stream for (path p, step s) is a pure function
of (seed, p, s), so any tiling of paths across blocks/devices draws the
*same* numbers — reproducibility is independent of the parallel
decomposition (this is also what makes the domain task divisible, the
property the paper's allocation relaxation (eq. 5) relies on).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["threefry2x32", "uniforms", "normal_pair"]

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
# NOTE: kept as a Python int (not a module-level jnp array) so that Pallas
# kernels using this module do not close over a device constant.
_PARITY = 0x1BD11BDA


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """20-round Threefry-2x32: (key0, key1, ctr0, ctr1) -> (out0, out1).

    All arguments are uint32 arrays (broadcastable); returns two uint32
    arrays of the broadcast shape.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(x0, jnp.uint32) + k0
    x1 = jnp.asarray(x1, jnp.uint32) + k1
    k2 = k0 ^ k1 ^ jnp.uint32(_PARITY)
    ks = (k0, k1, k2)
    for block in range(5):  # 5 x 4 = 20 rounds
        rots = _ROT[:4] if block % 2 == 0 else _ROT[4:]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        # key injection after each 4-round block
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + jnp.uint32(block + 1)
    return x0, x1


def uniforms(k0, k1, x0, x1):
    """Two U(0,1) floats per counter, strictly inside the open interval.

    The top 24 bits are used so the uint->float conversion is exact in
    float32 (values >= 2**24 would round and could push u to exactly 1.0,
    which poisons log(u) in Box-Muller).  The conversion goes through
    int32, which holds those 24 bits exactly: the TPU kernel compiler has
    no uint32 -> float32 cast.
    """
    a, b = threefry2x32(k0, k1, x0, x1)
    scale = jnp.float32(2.0**-24)

    def to_unit(bits):
        top = (bits >> jnp.uint32(8)).astype(jnp.int32)
        return (top.astype(jnp.float32) + jnp.float32(0.5)) * scale

    return to_unit(a), to_unit(b)


def normal_pair(k0, k1, x0, x1):
    """Two independent N(0,1) floats per counter via Box-Muller."""
    u0, u1 = uniforms(k0, k1, x0, x1)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u0))
    theta = jnp.float32(2.0 * 3.14159265358979) * u1
    return r * jnp.cos(theta), r * jnp.sin(theta)
