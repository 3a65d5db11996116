"""Parameter construction, sharding rules and numeric helpers.

Parameters are a *flat* ``dict[str, jnp.ndarray]`` with '/'-joined path
keys ("blocks/attn/wq", ...). Flat dicts keep sharding specs, optimizer
state, and checkpoint shards trivially alignable. Layer-stacked parameters
(for lax.scan over layers) carry a leading L dimension.

Sharding follows the MaxText-style FSDP x TP recipe on the
("data", "model") mesh (+ "pod" for pure DP in the multi-pod mesh):

  * weight matrices [d_in, d_out]-like: P("data", "model") — d_in sharded
    over the data axis (FSDP / ZeRO-3: XLA SPMD inserts per-layer
    all-gathers), d_out over the model axis (TP).
  * layer-boundary activations [B, S, D]: P(("pod","data"), SP?, None) —
    batch over DP axes; with sequence parallelism the S dim additionally
    shards over "model" between blocks.
  * axes are only sharded when divisible — ``maybe`` drops a mesh axis for
    dims it does not divide (e.g. 4 KV heads on a 16-way model axis).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ParamBuilder", "Rules", "flat_get", "subtree", "stack_init",
           "shard_act", "DEFAULT_DP", "MODEL", "remat_policy", "REMAT_POLICY"]

#: per-layer activation-checkpoint policy: "nothing" (recompute everything,
#: minimum memory) or "dots" (save matmul outputs — less recompute, more
#: HBM). A §Perf hillclimb lever; switch via repro.models.common.
REMAT_POLICY = "nothing"


def remat_policy():
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    return policies[REMAT_POLICY]

Params = dict[str, jnp.ndarray]

DEFAULT_DP: tuple[str, ...] = ("pod", "data")  # logical DP axes (pod may be absent)
MODEL = "model"


class Rules:
    """Axis-sharding helper bound to a concrete mesh axis-size mapping.

    ``axis_sizes`` maps axis name -> size; axes absent from the current
    mesh (e.g. "pod" on the single-pod mesh) must be pre-filtered by the
    caller via ``present``.
    """

    def __init__(self, axis_sizes: dict[str, int]):
        self.axis_sizes = dict(axis_sizes)

    def present(self, *axes: str) -> tuple[str, ...]:
        return tuple(a for a in axes if a in self.axis_sizes)

    def maybe(self, dim: int, *axes: str):
        """Return the (possibly compound) mesh axes for a dim, or None if
        the dim is not divisible by their product."""
        axes = self.present(*axes)
        if not axes:
            return None
        prod = math.prod(self.axis_sizes[a] for a in axes)
        if dim % prod != 0:
            return None
        return axes if len(axes) > 1 else axes[0]

    def dp(self) -> tuple[str, ...]:
        return self.present(*DEFAULT_DP)


#: Replicated rules used for single-device smoke tests.
REPLICATED = Rules({})


class ParamBuilder:
    """Initialises a flat param dict and its matching PartitionSpec dict."""

    def __init__(self, key: jax.Array, dtype):
        self._key = key
        self.dtype = dtype
        self.params: Params = {}
        self.specs: dict[str, P] = {}

    def _next(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def normal(self, name: str, shape: tuple[int, ...], spec: P,
               scale: float | None = None) -> None:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
        self.params[name] = (jax.random.normal(self._next(), shape, jnp.float32)
                             * scale).astype(self.dtype)
        self.specs[name] = spec

    def zeros(self, name: str, shape: tuple[int, ...], spec: P) -> None:
        self.params[name] = jnp.zeros(shape, self.dtype)
        self.specs[name] = spec

    def ones(self, name: str, shape: tuple[int, ...], spec: P) -> None:
        self.params[name] = jnp.ones(shape, self.dtype)
        self.specs[name] = spec

    def const(self, name: str, value, spec: P) -> None:
        self.params[name] = jnp.asarray(value, self.dtype)
        self.specs[name] = spec


def flat_get(params: Params, prefix: str) -> Params:
    """Sub-dict of keys under ``prefix/``, with the prefix stripped."""
    pre = prefix + "/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def subtree(params: Params, prefix: str) -> Params:
    return flat_get(params, prefix)


def stack_init(builder_fn: Callable[[jax.Array], tuple[Params, dict]],
               key: jax.Array, n: int) -> tuple[Params, dict]:
    """Initialise ``n`` copies of a layer stacked on a leading dim,
    prepending None to each spec (the layer-stack dim is never sharded).

    The copies come from one ``lax.map`` over per-layer keys: the layer is
    traced and compiled once, not ``n`` times, and the stacked arrays are
    written in place (no second copy while stacking)."""
    keys = jax.random.split(key, n)
    specs: dict[str, P] = {}

    def one(k):
        p, s = builder_fn(k)
        specs.update(s)
        return p

    stacked = jax.lax.map(one, keys)
    return stacked, {k: P(None, *tuple(sp)) for k, sp in specs.items()}


def shard_act(x: jnp.ndarray, spec: P | None, rules: "Rules | None" = None):
    """with_sharding_constraint that (a) is a no-op outside a mesh context
    and (b) drops spec axes that do not divide the dim (e.g. batch=1 decode
    cells on a 16-way data axis)."""
    if spec is None:
        return x
    if rules is not None:
        dims = list(spec) + [None] * (x.ndim - len(spec))
        fixed = []
        for size, ax in zip(x.shape, dims):
            if ax is None:
                fixed.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            prod = math.prod(rules.axis_sizes.get(a, 1) for a in axes)
            fixed.append(ax if prod and size % prod == 0 else None)
        spec = P(*fixed)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x
