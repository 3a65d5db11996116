"""Plain reference for the Monte Carlo pricing cell.

A straightforward ``jax.numpy`` simulation of the paper's Table 1 contracts
that draws the same random stream as the system under test: Threefry-2x32
(20 rounds) keyed by (seed, task id) with the counter (path, step), the top
24 bits of each word to a uniform in (0, 1), Box-Muller to two normals,
then a log-Euler step for Black-Scholes and a full-truncation Euler step
for Heston, with the running mean over the post-initial observations and
the min and max including the initial spot. Price and CI follow the
paper's convention: discounted mean payoff and 2 x 1.96 x the standard
error. With the same stream the program's prices agree with these to
float32 summation order, so a gap of a standard error means a wrong
stream, step, payoff or path count.

It imports nothing of the system under test; tasks arrive as plain
numbers (``task_row``).
"""
from __future__ import annotations

import functools
import math

__all__ = ["task_row", "moments", "price", "CHUNK"]

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA
#: Paths simulated at once; the reference loops over chunks of this size.
CHUNK = 1 << 17

EUROPEAN, ASIAN, BARRIER, DOUBLE_BARRIER, DIGITAL = range(5)


def task_row(task) -> dict:
    """A pricing task as plain numbers (the duck-typed fields of the
    paper's task: underlying, option, maturity, n_steps, task_id)."""
    u, o = task.underlying, task.option
    heston = hasattr(u, "kappa")
    return {
        "task_id": int(task.task_id), "n_steps": int(task.n_steps),
        "maturity": float(task.maturity), "heston": heston,
        "spot": float(u.spot), "rate": float(u.rate),
        "vol": 0.0 if heston else float(u.volatility),
        "v0": float(u.v0) if heston else 0.0,
        "kappa": float(u.kappa) if heston else 0.0,
        "theta": float(u.theta) if heston else 0.0,
        "xi": float(u.xi) if heston else 0.0,
        "rho": float(u.rho) if heston else 0.0,
        "payoff": int(o.payoff), "strike": float(o.strike),
        "lower": float(o.lower), "upper": float(o.upper),
        "payout": float(o.payout), "call": bool(o.call),
    }


def _threefry(k0, k1, x0, x1):
    import jax.numpy as jnp

    u32 = jnp.uint32
    k0, k1 = u32(k0), u32(k1)
    k2 = k0 ^ k1 ^ u32(_PARITY)
    ks = (k0, k1, k2)
    x0 = x0 + k0
    x1 = x1 + k1
    for block in range(5):
        rots = _ROT[:4] if block % 2 == 0 else _ROT[4:]
        for r in rots:
            x0 = x0 + x1
            x1 = ((x1 << u32(r)) | (x1 >> u32(32 - r))) ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + u32(block + 1)
    return x0, x1


def _normals(k0, k1, paths, step, dtype):
    import jax.numpy as jnp

    a, b = _threefry(k0, k1, paths, jnp.broadcast_to(step, paths.shape))

    def unit(bits):
        top = (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
        return (top + 0.5) * jnp.float32(2.0 ** -24)

    u0, u1 = unit(a), unit(b)
    r = jnp.sqrt(-2.0 * jnp.log(u0))
    th = jnp.float32(2.0 * 3.14159265358979) * u1
    return (r * jnp.cos(th)).astype(dtype), (r * jnp.sin(th)).astype(dtype)


_FLOATS = ("spot", "rate", "vol", "v0", "kappa", "theta", "xi", "rho",
           "strike", "lower", "upper", "payout")


@functools.lru_cache(maxsize=None)
def _chunk_fn(heston: bool, n_steps: int, dtype_name: str):
    """One jitted chunk of paths for a model kind and step count; every
    other task number is a traced operand."""
    import jax
    import jax.numpy as jnp

    dt_ = jnp.dtype(dtype_name)

    @jax.jit
    def chunk(seed, task_id, offset, n_active, maturity, kind, call, p):
        p = {k: v.astype(dt_) for k, v in p.items()}
        dt = (maturity / n_steps).astype(dt_)
        sqrt_dt = jnp.sqrt(dt)
        paths = offset + jnp.arange(CHUNK, dtype=jnp.uint32)
        spot = jnp.full((CHUNK,), p["spot"])
        v0 = jnp.full((CHUNK,), p["v0"])

        def body(state, step):
            s, v, acc, mn, mx = state
            z1, z2 = _normals(seed, task_id, paths, step, dt_)
            if heston:
                rho_c = jnp.sqrt(jnp.maximum(1.0 - p["rho"] * p["rho"], 0.0))
                zv = p["rho"] * z1 + rho_c * z2
                vp = jnp.maximum(v, 0.0).astype(dt_)
                sv = jnp.sqrt(vp)
                s = s * jnp.exp((p["rate"] - 0.5 * vp) * dt + sv * sqrt_dt * z1)
                v = (v + p["kappa"] * (p["theta"] - vp) * dt
                     + p["xi"] * sv * sqrt_dt * zv)
            else:
                drift = (p["rate"] - 0.5 * p["vol"] * p["vol"]) * dt
                s = s * jnp.exp(drift + p["vol"] * sqrt_dt * z1)
            return (s, v, acc + s, jnp.minimum(mn, s), jnp.maximum(mx, s)), None

        init = (spot, v0, jnp.zeros_like(spot), spot, spot)
        (s, _, acc, mn, mx), _ = jax.lax.scan(
            body, init, jnp.arange(n_steps, dtype=jnp.uint32))
        f32 = jnp.float32
        s, avg, mn, mx = (x.astype(f32) for x in (s, acc / n_steps, mn, mx))
        q = {k: v.astype(f32) for k, v in p.items()}
        sign = jnp.where(call, 1.0, -1.0)
        vanilla = jnp.maximum(sign * (s - q["strike"]), 0.0)
        alive_up = mx < q["upper"]
        alive = alive_up & (mn > q["lower"])
        pay = jnp.select(
            [kind == EUROPEAN, kind == ASIAN, kind == BARRIER,
             kind == DOUBLE_BARRIER],
            [vanilla, jnp.maximum(sign * (avg - q["strike"]), 0.0),
             jnp.where(alive_up, vanilla, 0.0), jnp.where(alive, vanilla, 0.0)],
            jnp.where(alive, q["payout"], 0.0))
        pay = jnp.where(paths < n_active, pay, 0.0).astype(f32)
        return pay.sum(), (pay * pay).sum()

    return chunk


def moments(row: dict, n_paths: int, seed: int, dtype: str = "float32"):
    """(sum of payoffs, sum of squares, n_paths) over the first
    ``n_paths`` paths of the (seed, task) stream; sums kept in float64
    across chunks."""
    import jax.numpy as jnp

    chunk = _chunk_fn(row["heston"], row["n_steps"], dtype)
    p = {k: jnp.float32(row[k]) for k in _FLOATS}
    s = s2 = 0.0
    for off in range(0, n_paths, CHUNK):
        a, b = chunk(jnp.uint32(seed), jnp.uint32(row["task_id"]),
                     jnp.uint32(off), jnp.uint32(n_paths),
                     jnp.float32(row["maturity"]), jnp.int32(row["payoff"]),
                     jnp.bool_(row["call"]), p)
        s += float(a)
        s2 += float(b)
    return s, s2, n_paths


def price(row: dict, n_paths: int, seed: int, dtype: str = "float32"):
    """(price, ci95) at the paper's convention."""
    s, s2, n = moments(row, n_paths, seed, dtype)
    mean = s / n
    var = max(s2 / n - mean * mean, 0.0)
    disc = math.exp(-row["rate"] * row["maturity"])
    return disc * mean, 2 * 1.96 * disc * math.sqrt(var / n)
