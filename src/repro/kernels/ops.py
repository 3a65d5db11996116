"""jit'd public wrappers around the Pallas Monte Carlo kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.pricing.contracts import PricingTask, TaskBatch
from repro.pricing.mc import record_trace
from .mc_paths import (
    DEFAULT_BLOCK_PATHS,
    mc_moments_batch_kernel_call,
)

__all__ = ["mc_moments", "mc_moments_batch", "default_interpret"]


def default_interpret() -> bool:
    """Interpret the Pallas kernels only on JAX's CPU backend.

    Everywhere else the kernel is compiled: a device that cannot compile it
    fails loudly instead of falling back to the interpreter.
    """
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit,
                   static_argnames=("n_paths_max", "block_paths", "interpret"))
def _mc_moments_batch_jit(batch: TaskBatch, n_active, seed, *,
                          n_paths_max: int, block_paths: int, interpret: bool):
    record_trace("pallas_batch")
    tiles = mc_moments_batch_kernel_call(
        batch, n_active, seed, n_paths_max=n_paths_max,
        block_paths=block_paths, interpret=interpret)
    return tiles[:, 0].sum(axis=1), tiles[:, 1].sum(axis=1)


def mc_moments_batch(batch: TaskBatch, n_active, seed: int = 0,
                     block_paths: int | None = None,
                     interpret: bool | None = None):
    """Per-task (sum payoff, sum payoff^2) for a task family, one launch.

    ``n_active`` is a per-task path-count sequence; it is padded up to a
    whole number of path blocks (masked inside the kernel), so the compiled
    executable depends only on (family, padded shape, block_paths) — the
    whole benchmarking ladder of a characterisation run reuses it.
    """
    if block_paths is None:
        block_paths = DEFAULT_BLOCK_PATHS
    if interpret is None:
        interpret = default_interpret()
    n_act = np.asarray(n_active, dtype=np.uint32).reshape(-1)
    n_max = int(n_act.max())
    n_pad = max(-(-n_max // block_paths), 1) * block_paths
    return _mc_moments_batch_jit(
        batch, jnp.asarray(n_act), jnp.asarray([seed], jnp.uint32),
        n_paths_max=n_pad, block_paths=block_paths, interpret=interpret)


def mc_moments(task: PricingTask, n_paths: int, seed: int = 0,
               block_paths: int | None = None, interpret: bool | None = None):
    """(sum payoff, sum payoff^2) over ``n_paths`` paths via the TPU kernel.

    A thin wrapper over a batch of one: task parameters are runtime
    operands, so pricing N tasks of one family compiles once, not N times.
    Combined with ``repro.pricing.mc._finalize`` this yields price + 95% CI.
    """
    batch = TaskBatch.from_tasks([task])
    sums, sqs = mc_moments_batch(batch, [n_paths], seed,
                                 block_paths=block_paths, interpret=interpret)
    return sums[0], sqs[0]
