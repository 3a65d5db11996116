"""Pallas TPU kernels: Monte Carlo path simulation + payoff moments.

The per-platform compute hot-spot the paper accelerates (F3's OpenCL/Max
back-ends) re-thought for the TPU memory hierarchy:

  * Grid over *path blocks*: each program instance owns a
    (SUBLANES x LANES)-shaped tile of paths that stays resident in
    VMEM/VREGs for the entire time loop — path state never touches HBM.
  * RNG is counter-based Threefry-2x32 (repro.kernels.prng) computed
    in-register on the VPU: no RNG state to load/store, and the stream for
    (path, step) is identical no matter how paths are tiled across blocks
    or devices.
  * The only HBM traffic is the output: per task one lane-dense (8, 128)
    tile of per-lane (sum payoff, sum payoff^2), resident in VMEM across
    that task's path blocks — the kernel is pure-compute by construction.
  * Payoffs need only 4 path statistics (terminal, mean, min, max), all
    accumulated in registers, so one kernel serves every Table 1 contract.

GPU-vs-TPU adaptation note: F3's GPU back-end is thread-per-path with a
block-level tree reduction in shared memory. On TPU the natural unit is
the (8, 128) VREG tile; the reduction is a free vector reduce at the end
of the block. There is no warp-shuffle analogue to port — the VPU's dense
2-D tiles make the GPU trick unnecessary.

Block-shape trade-off (VMEM): state per path is 6 f32 scalars for Heston
(S, v, acc, mn, mx + normals) -> a (32, 128) tile costs ~100 KiB of
VREG/VMEM working set, far under the ~16 MiB/core budget; larger tiles
amortise grid overhead until register pressure spills. ops.py exposes
``block_paths`` so the sweep in tests/benchmarks can pick the knee.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prng import normal_pair
from repro.pricing.contracts import (
    COL,
    BlackScholes,
    Heston,
    PricingTask,
    TaskBatch,
    bs_step_fn,
    heston_step_fn,
    payoff_from_stats,
    payoff_from_stats_coded,
)

__all__ = [
    "mc_moments_kernel_call", "mc_moments_batch_kernel_call",
    "validate_blocking", "SUBLANES", "LANES", "DEFAULT_BLOCK_PATHS",
]

SUBLANES = 8
LANES = 128

#: The one path-tile default shared by every engine entry point
#: (``mc.price``/``price_batch``, ``ops.mc_moments``, the kernel calls).
#:
#: VMEM trade-off: state per path is <= 6 f32 scalars (Heston: S, v, acc,
#: mn, mx + a normal pair), so a 1024-path block is an (8, 128) VREG tile
#: stack costing ~24 KiB of working set — far under the ~16 MiB/core VMEM
#: budget, while already amortising grid overhead; larger tiles buy little
#: until they start spilling registers, and smaller ones multiply dispatch
#: overhead.  Tests/benchmarks sweep ``block_paths`` explicitly to probe
#: the knee; production callers take this default.
DEFAULT_BLOCK_PATHS = 1024


def validate_blocking(n_paths: int, block_paths: int) -> int:
    """The single divisibility check for path tiling; returns #blocks."""
    if block_paths % LANES:
        raise ValueError(f"block_paths={block_paths} must be a multiple of {LANES}")
    if n_paths % block_paths:
        raise ValueError(
            f"n_paths={n_paths} must be a multiple of block_paths={block_paths}")
    return n_paths // block_paths


def _mc_kernel(o_ref, *, task: PricingTask, seed: int, block_paths: int,
               n_steps: int):
    """One grid step: simulate ``block_paths`` paths, write (sum, sumsq).

    The path tile is shaped (block_paths // LANES, LANES) — a stack of VREG
    rows. All state lives in the fori_loop carry (registers/VMEM).
    """
    u = task.underlying
    dt = task.maturity / n_steps
    rows = block_paths // LANES
    block = pl.program_id(0)

    # global path ids for this block: (rows, LANES) uint32
    base = block * block_paths
    pid = (base
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 1))
    k0 = jnp.uint32(seed)
    k1 = jnp.uint32(task.task_id)

    spot = jnp.full((rows, LANES), jnp.float32(u.spot))

    if isinstance(u, BlackScholes):
        f = bs_step_fn(jnp.float32(u.rate), jnp.float32(u.volatility),
                       jnp.float32(dt))

        def step(s_idx, state):
            s, acc, mn, mx = state
            z = normal_pair(k0, k1, pid, jnp.full_like(pid, s_idx))
            s = f(s, z)
            return s, acc + s, jnp.minimum(mn, s), jnp.maximum(mx, s)

        init: Any = (spot, jnp.zeros_like(spot), spot, spot)
        s_t, acc, mn, mx = jax.lax.fori_loop(0, n_steps, step, init)
    else:
        f = heston_step_fn(jnp.float32(u.rate), jnp.float32(u.kappa),
                           jnp.float32(u.theta), jnp.float32(u.xi),
                           jnp.float32(u.rho), jnp.float32(dt))

        def step(s_idx, state):
            s, v, acc, mn, mx = state
            z = normal_pair(k0, k1, pid, jnp.full_like(pid, s_idx))
            s, v = f((s, v), z)
            return s, v, acc + s, jnp.minimum(mn, s), jnp.maximum(mx, s)

        init = (spot, jnp.full((rows, LANES), jnp.float32(u.v0)),
                jnp.zeros_like(spot), spot, spot)
        s_t, _, acc, mn, mx = jax.lax.fori_loop(0, n_steps, step, init)

    avg = acc / jnp.float32(n_steps)
    pay = payoff_from_stats(s_t, avg, mn, mx, task.option)
    o_ref[0, 0] = jnp.sum(pay)
    o_ref[0, 1] = jnp.sum(pay * pay)


def mc_moments_kernel_call(task: PricingTask, n_paths: int, seed: int,
                           block_paths: int = DEFAULT_BLOCK_PATHS,
                           interpret: bool = True):
    """pallas_call wrapper: returns per-block (sum, sumsq) of shape (blocks, 2).

    ``interpret=True`` executes the kernel body in Python on CPU (this
    container has no TPU); on real hardware pass ``interpret=False``.

    This is the legacy single-task kernel (task baked in as a static trace
    constant — one compile per task).  Production paths go through
    :func:`mc_moments_batch_kernel_call`, which takes task parameters as
    runtime SMEM operands and compiles once per task family.
    """
    blocks = validate_blocking(n_paths, block_paths)

    kernel = functools.partial(
        _mc_kernel, task=task, seed=seed, block_paths=block_paths,
        n_steps=task.n_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        out_specs=pl.BlockSpec((1, 2), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, 2), jnp.float32),
        interpret=interpret,
    )()


# --------------------------------------------------------------------------
# Batched runtime-parameter kernel: one compile per task family
# --------------------------------------------------------------------------

def _mc_batch_kernel(params_ref, tid_ref, kind_ref, nact_ref, seed_ref, o_ref,
                     *, model_kind: str, block_paths: int, n_steps: int):
    """One (task, path-block) grid step of the family-batched kernel.

    Per-task scalars (spot, rate, dt, vol/Heston params, strike, barriers,
    payout, call sign) sit in SMEM as whole arrays indexed by
    ``pl.program_id(0)`` — they are *runtime operands*, so the compiled
    kernel is shared by every task of the family.  The path tile design is
    unchanged from the single-task kernel: a (block_paths // LANES, LANES)
    stack of VREG rows resident for the whole time loop.

    Each task owns one lane-dense (SUBLANES, LANES) output tile that stays
    resident across its path blocks (the ``"arbitrary"`` grid axis): row 0
    accumulates per-lane sums of the payoff, row 1 of its square.

    Paths with global id >= n_active (batch padding for ragged per-task
    path counts) are simulated but masked out of the payoff sums, so each
    task's moments are exactly those of its first n_active counter-based
    draws — bit-identical in distribution to the per-task run.
    """
    rows = block_paths // LANES
    task = pl.program_id(0)
    block = pl.program_id(1)

    base = (block * block_paths).astype(jnp.uint32)
    pid = (base
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 1))
    k0 = seed_ref[0]
    k1 = tid_ref[task]

    def param(name):
        return params_ref[task, COL[name]]

    spot = jnp.full((rows, LANES), param("spot"))
    rate = param("rate")
    dt = param("dt")

    if model_kind == "black-scholes":
        f = bs_step_fn(rate, param("vol"), dt)

        def step(s_idx, state):
            s, acc, mn, mx = state
            z = normal_pair(k0, k1, pid, jnp.full_like(pid, s_idx))
            s = f(s, z)
            return s, acc + s, jnp.minimum(mn, s), jnp.maximum(mx, s)

        init: Any = (spot, jnp.zeros_like(spot), spot, spot)
        s_t, acc, mn, mx = jax.lax.fori_loop(0, n_steps, step, init)
    else:
        f = heston_step_fn(rate, param("kappa"), param("theta"), param("xi"),
                           param("rho"), dt)

        def step(s_idx, state):
            s, v, acc, mn, mx = state
            z = normal_pair(k0, k1, pid, jnp.full_like(pid, s_idx))
            s, v = f((s, v), z)
            return s, v, acc + s, jnp.minimum(mn, s), jnp.maximum(mx, s)

        init = (spot, jnp.full((rows, LANES), param("v0")),
                jnp.zeros_like(spot), spot, spot)
        s_t, _, acc, mn, mx = jax.lax.fori_loop(0, n_steps, step, init)

    avg = acc / jnp.float32(n_steps)
    pay = payoff_from_stats_coded(
        s_t, avg, mn, mx,
        strike=param("strike"), lower=param("lower"), upper=param("upper"),
        payout=param("payout"), call_sign=param("call_sign"),
        kind=kind_ref[task])
    pay = jnp.where(pid < nact_ref[task], pay, jnp.float32(0.0))

    @pl.when(block == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane_sum = jnp.sum(pay, axis=0, keepdims=True)
    lane_sq = jnp.sum(pay * pay, axis=0, keepdims=True)
    o_ref[...] += jnp.where(row == 0, lane_sum,
                            jnp.where(row == 1, lane_sq, jnp.float32(0.0)))


def mc_moments_batch_kernel_call(batch: TaskBatch, n_active, seed,
                                 n_paths_max: int,
                                 block_paths: int = DEFAULT_BLOCK_PATHS,
                                 interpret: bool = True):
    """Family-batched pallas_call over a 2-D grid (task, path_block).

    ``n_active`` is a (T,) uint32 array of per-task path counts;
    ``n_paths_max`` (a multiple of ``block_paths``) sets the padded grid.
    ``seed`` is a (1,) uint32 array — a runtime operand, so re-seeding the
    benchmark ladder never retraces.  Returns (T, SUBLANES, LANES) tiles
    whose row 0 holds per-lane payoff sums and row 1 per-lane sums of
    squares; every other row is zero.
    """
    blocks = validate_blocking(n_paths_max, block_paths)
    T = batch.n_tasks

    kernel = functools.partial(
        _mc_batch_kernel, model_kind=batch.model_kind,
        block_paths=block_paths, n_steps=batch.n_steps,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads
    return pl.pallas_call(
        kernel,
        grid=(T, blocks),
        in_specs=[smem] * 5,  # params, task_id, payoff kind, n_active, seed
        out_specs=pl.BlockSpec((None, SUBLANES, LANES), lambda t, b: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, SUBLANES, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(batch.params, batch.task_ids, batch.payoff_kinds,
      jnp.asarray(n_active, jnp.uint32), jnp.asarray(seed, jnp.uint32))
