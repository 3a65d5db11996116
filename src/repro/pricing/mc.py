"""Monte Carlo pricing engine (paper §4.1.3's F3 execution layer, in JAX).

Three backends, all drawing the *same* Threefry stream per (task, path,
step) so results agree across decompositions:

  * ``path_stats`` / ``price``          — pure jnp (lax.scan), the oracle
  * ``price(..., backend="pallas")``    — Pallas TPU kernels (repro.kernels)
  * ``price_sharded``                   — shard_map over a mesh axis; each
        device simulates a disjoint path range and partial moments are
        combined with psum (the domain's "divisible task" property,
        eq. 5, realised as data parallelism)

The engine returns the two domain metrics directly: the price estimate and
the 95% confidence interval (the *accuracy* metric, eq. 8).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.obs.trace import default_tracer
from repro.kernels.prng import normal_pair
from .contracts import (
    COL,
    BlackScholes,
    Heston,
    Option,
    PricingTask,
    TaskBatch,
    bs_step_fn,
    group_by_launch,
    heston_step_fn,
    payoff_from_stats,
    payoff_from_stats_coded,
)

__all__ = [
    "path_stats", "price", "price_batch", "price_sharded", "PriceResult",
    "trace_counts", "reset_trace_counts",
]


# --------------------------------------------------------------------------
# Trace accounting
# --------------------------------------------------------------------------
#
# Each traced function bumps a counter in its Python body, which runs only
# when jax (re)traces — jit cache hits never touch it.  Tests assert the
# batched engine compiles O(#families) times for a multi-task characterise
# instead of O(#tasks x #rungs).

_TRACE_COUNTS: collections.Counter = collections.Counter()


def record_trace(name: str) -> None:
    _TRACE_COUNTS[name] += 1


def trace_counts() -> dict[str, int]:
    """Snapshot of {engine name: number of traces} since the last reset."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


# --------------------------------------------------------------------------
# Path simulation (pure jnp — this IS the oracle the kernels are tested on)
# --------------------------------------------------------------------------

def _bs_step(u: BlackScholes, dt: float):
    f = bs_step_fn(jnp.float32(u.rate), jnp.float32(u.volatility),
                   jnp.float32(dt))

    def step(carry, inputs):
        return f(carry, inputs), carry

    return step


def _heston_step(u: Heston, dt: float):
    f = heston_step_fn(jnp.float32(u.rate), jnp.float32(u.kappa),
                       jnp.float32(u.theta), jnp.float32(u.xi),
                       jnp.float32(u.rho), jnp.float32(dt))

    def step(carry, inputs):
        new = f(carry, inputs)
        return new, carry[0]

    return step


def path_stats(task: PricingTask, n_paths: int, seed: int, path_offset: int = 0):
    """Simulate ``n_paths`` paths; return (s_t, avg, mn, mx), each (n_paths,).

    Conventions (shared with the kernels): the running average is over the
    n_steps post-initial observations; min/max include the initial spot.
    The RNG counter is (path_index, step); the key is (seed, task_id), so
    the draw for a given (task, path, step) is decomposition-independent.
    """
    u = task.underlying
    dt = task.maturity / task.n_steps
    paths = jnp.asarray(path_offset, jnp.uint32) + jnp.arange(n_paths, dtype=jnp.uint32)
    k0 = jnp.uint32(seed)
    k1 = jnp.uint32(task.task_id)
    steps = jnp.arange(task.n_steps, dtype=jnp.uint32)

    # Draw this step's normals from the (path, step) counter.
    def normals(step_idx):
        return normal_pair(k0, k1, paths, jnp.broadcast_to(step_idx, paths.shape))

    spot = jnp.full((n_paths,), jnp.float32(u.spot))
    if isinstance(u, BlackScholes):
        step_fn = _bs_step(u, dt)
        carry0: Any = spot
    else:
        step_fn = _heston_step(u, dt)
        carry0 = (spot, jnp.full((n_paths,), jnp.float32(u.v0)))

    def body(state, step_idx):
        carry, acc, mn, mx = state
        z = normals(step_idx)
        new_carry, _ = step_fn(carry, z)
        s_new = new_carry[0] if isinstance(new_carry, tuple) else new_carry
        acc = acc + s_new
        mn = jnp.minimum(mn, s_new)
        mx = jnp.maximum(mx, s_new)
        return (new_carry, acc, mn, mx), None

    # Carry running (sum, min, max) instead of materialising the whole
    # (n_steps, n_paths) path matrix: O(paths) memory at any path count.
    state0 = (carry0, jnp.zeros_like(spot), spot, spot)
    (carry, acc, mn, mx), _ = jax.lax.scan(body, state0, steps)
    s_t = carry[0] if isinstance(carry, tuple) else carry
    avg = acc / jnp.float32(task.n_steps)
    return s_t, avg, mn, mx


def _moments(task: PricingTask, n_paths: int, seed: int, path_offset: int = 0):
    """Partial sums (sum payoff, sum payoff^2) — the mergeable statistic."""
    s_t, avg, mn, mx = path_stats(task, n_paths, seed, path_offset)
    pay = payoff_from_stats(s_t, avg, mn, mx, task.option)
    return pay.sum(), (pay * pay).sum()


@functools.partial(dataclasses.dataclass, frozen=True)
class PriceResult:
    price: Any
    ci95: Any          # the paper's accuracy metric: size of the 95% CI
    std_error: Any
    n_paths: int

    def __repr__(self):
        return (f"PriceResult(price={float(self.price):.6f}, "
                f"ci95={float(self.ci95):.6f}, n={int(self.n_paths)})")


def _finalize(task: PricingTask, pay_sum, pay_sq, n) -> PriceResult:
    n = jnp.float32(n)
    mean = pay_sum / n
    var = jnp.maximum(pay_sq / n - mean * mean, 0.0)
    disc = jnp.float32(task.discount)
    stderr = disc * jnp.sqrt(var / n)
    return PriceResult(price=disc * mean, ci95=jnp.float32(2 * 1.96) * stderr,
                       std_error=stderr, n_paths=n)


# --------------------------------------------------------------------------
# Batched runtime-parameter engine: one compilation per task family
# --------------------------------------------------------------------------
#
# Task parameters enter as traced arrays (TaskBatch) and the path count is
# a traced chunk count (fixed-size chunks, fori_loop), so the XLA cache key
# is only (model kind, n_steps, batch size) — pricing a 128-task Table 1
# workload compiles ~2 times (one per underlying model), and the whole
# benchmarking ladder of a characterisation run rides the same executable.

#: Fixed path-chunk width of the jnp batched oracle.  The chunk shape is
#: what XLA compiles; the number of chunks is a runtime loop bound, so any
#: n_paths reuses the executable.  512 keeps the (T, 512) working set tiny
#: while leaving path-count latency resolution finer than the benchmark
#: ladders use.
CHUNK_PATHS = 512


def _batch_path_stats(batch: TaskBatch, n_paths: int, seed, path_offset=0):
    """Simulate every task in the batch over ``n_paths`` paths.

    Returns (s_t, avg, mn, mx), each (T, n_paths).  The RNG counter
    convention is unchanged — key (seed, task_id), counter (path, step) —
    so each task's draws are bit-identical to its per-task run.
    ``path_offset`` shifts the global path ids (chunked execution).
    """
    n_steps = batch.n_steps
    paths = (jnp.asarray(path_offset, jnp.uint32)
             + jnp.arange(n_paths, dtype=jnp.uint32))
    steps = jnp.arange(n_steps, dtype=jnp.uint32)
    k0 = jnp.asarray(seed, jnp.uint32)

    def one_task(prow, tid):
        spot = jnp.full((n_paths,), prow[COL["spot"]])
        dt = prow[COL["dt"]]
        rate = prow[COL["rate"]]

        def normals(step_idx):
            return normal_pair(k0, tid, paths, jnp.broadcast_to(step_idx, paths.shape))

        if batch.model_kind == "black-scholes":
            step_fn = bs_step_fn(rate, prow[COL["vol"]], dt)

            def s_of(carry):
                return carry

            carry0: Any = spot
        else:
            step_fn = heston_step_fn(rate, prow[COL["kappa"]],
                                     prow[COL["theta"]], prow[COL["xi"]],
                                     prow[COL["rho"]], dt)

            def s_of(carry):
                return carry[0]

            carry0 = (spot, jnp.full((n_paths,), prow[COL["v0"]]))

        def body(state, step_idx):
            carry, acc, mn, mx = state
            carry = step_fn(carry, normals(step_idx))
            s_new = s_of(carry)
            return (carry, acc + s_new, jnp.minimum(mn, s_new),
                    jnp.maximum(mx, s_new)), None

        state0 = (carry0, jnp.zeros_like(spot), spot, spot)
        (carry, acc, mn, mx), _ = jax.lax.scan(body, state0, steps)
        return s_of(carry), acc / jnp.float32(n_steps), mn, mx

    return jax.vmap(one_task)(batch.params, batch.task_ids)


def _batch_moments_impl(batch: TaskBatch, n_active, n_chunks, seed, *,
                        chunk_paths: int):
    """Per-task (sum payoff, sum payoff^2), masked to each task's n_active.

    Paths are simulated in fixed (T, chunk_paths) chunks inside a fori_loop
    whose bound ``n_chunks`` is a *runtime* scalar, so the compiled shape
    never depends on the requested path count — one executable serves the
    whole benchmark ladder and any execution-time shard size.  Because the
    RNG is counter-based on the global path index, chunking is invisible to
    the statistics (the same decomposition-independence price_sharded
    relies on).
    """
    record_trace("jnp_batch")
    p = batch.params
    T = batch.n_tasks
    zeros = jnp.zeros((T,), jnp.float32)

    def chunk_body(c, acc):
        sums, sqs = acc
        offset = (c * chunk_paths).astype(jnp.uint32)
        s_t, avg, mn, mx = _batch_path_stats(batch, chunk_paths, seed,
                                             path_offset=offset)
        pay = payoff_from_stats_coded(
            s_t, avg, mn, mx,
            strike=p[:, COL["strike"], None], lower=p[:, COL["lower"], None],
            upper=p[:, COL["upper"], None], payout=p[:, COL["payout"], None],
            call_sign=p[:, COL["call_sign"], None],
            kind=batch.payoff_kinds[:, None])
        pid = offset + jnp.arange(chunk_paths, dtype=jnp.uint32)
        mask = pid[None, :] < n_active[:, None]
        pay = jnp.where(mask, pay, jnp.float32(0.0))
        return sums + pay.sum(axis=1), sqs + (pay * pay).sum(axis=1)

    return jax.lax.fori_loop(0, n_chunks, chunk_body, (zeros, zeros))


_batch_moments = jax.jit(_batch_moments_impl, static_argnames=("chunk_paths",))

#: Max spread of per-task path counts co-batched into one padded launch.
#: Padding waste per task is bounded by this factor; splitting costs at
#: most one extra trace per distinct sub-batch size, which the runtime-n
#: chunk loop keeps rare.
_RAGGED_RATIO = 4


def _ragged_buckets(ns: Sequence[int]) -> list[list[int]]:
    """Partition positions of ``ns`` into buckets with max/min <= ratio.

    Greedy over ascending counts; uniform inputs (the common case) always
    yield a single bucket.  Returns lists of positions into ``ns``.
    """
    order = sorted(range(len(ns)), key=lambda k: ns[k])
    buckets: list[list[int]] = []
    bucket_min = None
    for k in order:
        n = max(int(ns[k]), 1)
        if bucket_min is None or n > bucket_min * _RAGGED_RATIO:
            buckets.append([])
            bucket_min = n
        buckets[-1].append(k)
    return buckets


def price_batch(tasks: Sequence[PricingTask], n_paths,
                seed: int = 0, backend: str = "jnp",
                block_paths: int | None = None) -> list[PriceResult]:
    """Price many tasks with one compiled launch per compilation group.

    ``n_paths`` is an int (shared by all tasks) or a per-task sequence;
    ragged path counts within a group are padded (to the next chunk for the
    jnp oracle, path block for the Pallas kernel) and masked, so every
    task's estimate uses exactly its own first ``n`` counter-based draws —
    identical in distribution to a per-task run.

    Tasks are grouped by :func:`launch_key` — (model kind, n_steps), the
    only *structural* task properties — so a full mixed Table 1 workload
    needs two compiled executables, and re-pricing any same-shaped workload
    needs none.  Within a group, wildly ragged path counts are split into
    magnitude buckets (max/min <= ``_RAGGED_RATIO``) before padding, so a
    64-path shard never simulates a co-batched task's million paths; the
    uniform-n hot paths (benchmark ladders, calibration) stay one launch.
    Each bucket is a ``pricing.pack`` span (grouping and packing) and a
    ``pricing.finalize`` span (the per-task moments) on the process tracer.

    Returns one :class:`PriceResult` per task, in input order.
    """
    tasks = list(tasks)
    ns = np.broadcast_to(np.asarray(n_paths, dtype=np.int64), (len(tasks),))
    results: list[PriceResult | None] = [None] * len(tasks)
    span = default_tracer().span
    for _key, group in group_by_launch(tasks):
        for bucket in _ragged_buckets([int(ns[i]) for i, _ in group]):
            with span("pricing.pack", track="pricing", tasks=len(bucket)):
                sub = [group[k] for k in bucket]
                batch = TaskBatch.from_tasks([t for _, t in sub])
                n_act = np.asarray([ns[i] for i, _ in sub], dtype=np.uint32)
            if backend == "pallas":
                from repro.kernels import ops  # local import: kernels are optional

                sums, sqs = ops.mc_moments_batch(batch, n_act, seed,
                                                 block_paths=block_paths)
            else:
                n_chunks = -(-int(n_act.max()) // CHUNK_PATHS)
                sums, sqs = _batch_moments(batch, jnp.asarray(n_act),
                                           jnp.int32(n_chunks),
                                           jnp.uint32(seed),
                                           chunk_paths=CHUNK_PATHS)
            with span("pricing.finalize", track="pricing", tasks=len(sub)):
                for k, (i, t) in enumerate(sub):
                    results[i] = _finalize(t, sums[k], sqs[k], int(ns[i]))
    return results  # type: ignore[return-value]


def price(task: PricingTask, n_paths: int, seed: int = 0,
          backend: str = "jnp", block_paths: int | None = None) -> PriceResult:
    """Price one task — a thin wrapper over a batch of one.

    ``backend`` in {"jnp", "pallas"}.  The CI convention follows the paper:
    accuracy = *size* of the 95% interval (2 x 1.96 x stderr), in pricing
    currency.  Because task parameters are runtime operands, repeated calls
    across a task family reuse one compiled executable.
    """
    return price_batch([task], n_paths, seed=seed, backend=backend,
                       block_paths=block_paths)[0]


# --------------------------------------------------------------------------
# Distributed pricing: shard_map over a mesh axis
# --------------------------------------------------------------------------

def price_sharded(task: PricingTask, n_paths: int, mesh: Mesh,
                  axis: str = "data", seed: int = 0) -> PriceResult:
    """Split paths across ``mesh[axis]``; merge partial moments with psum.

    Because the RNG is counter-based on the *global* path index, the result
    is bit-identical in distribution to the single-device run (up to float
    reduction order) for any device count — the allocation layer may
    re-split tasks freely (eq. 5) without statistical consequences.
    """
    n_dev = mesh.shape[axis]
    if n_paths % n_dev:
        raise ValueError(f"n_paths={n_paths} not divisible by mesh[{axis}]={n_dev}")
    local = n_paths // n_dev

    def worker():
        idx = jax.lax.axis_index(axis)
        offset = (idx * local).astype(jnp.uint32)
        s, s2 = _moments(task, local, seed, path_offset=offset)
        return jax.lax.psum(s, axis), jax.lax.psum(s2, axis)

    spec = P()  # fully replicated scalars
    # Replication checking is off (see repro.compat.shard_map): the scan
    # carry starts replicated and becomes varying through the
    # axis_index-derived path offset, which the static check cannot see.
    fn = compat.shard_map(worker, mesh=mesh, in_specs=(), out_specs=(spec, spec))
    pay_sum, pay_sq = jax.jit(fn)()
    return _finalize(task, pay_sum, pay_sq, n_paths)
