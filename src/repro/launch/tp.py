"""Tensor-parallel serving step functions (gather-based).

The sharded step is the dense model's own forward
(``DenseModel.prefill`` / ``decode_step``) run per device under
``shard_map``; this module holds only the layouts and the wrapping.

The sharded path must compute the single-device engine's logits — it is
the same platform quoted at a different mesh shape, and the allocator's
accountability story dies the moment "same work, wider mesh" changes the
answer. psum-based (Megatron-style row-parallel) output projections split
each contraction across devices and add the partial sums; here only
*column-parallel* weights (q/k/v heads, MLP hidden, unembed vocab) are
sharded, and the forward **all-gathers activations** back to full width
where a contraction meets a shard (attention output before ``wo``, MLP
hidden before ``w_out``, the logits: ``models.layers.gather_shards``),
so those matmuls run replicated. A tiled all-gather concatenates shards
in axis order, so gathered tensors are elementwise identical to their
dense layout and every op computes the same per-element arithmetic
as the dense path.

That is not a bitwise guarantee: the compiler may pick other dot and
fusion strategies for the partitioned program's smaller shapes, which
reorders float sums (XLA:CPU in JAX 0.9 does so for the per-device
attention with one kv head: a few float32 ulps). Tests therefore hold the
sharded logits to a float tolerance and identical greedy tokens.

The KV cache shards on the kv-head axis — the genuine pooled-KV win —
which requires ``n_kv_heads % tp == 0``; GQA head groups then stay
contiguous per device (device ``p`` holds q heads ``[p*h/tp, ...)`` and
exactly their kv heads). Other widths raise :class:`TPShardingError`
(kv-head *replication* for tp > n_kv_heads is not offered).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map

__all__ = ["TPShardingError", "tp_param_specs", "tp_cache_specs",
           "build_tp_step_fns", "validate_tp", "exchange_counts"]

MODEL = "model"


class TPShardingError(ValueError):
    """The model's shapes cannot be tensor-parallelised at this width."""


def validate_tp(cfg, tp: int) -> None:
    if tp < 2:
        raise TPShardingError(f"tensor-parallel width must be >= 2, got {tp}")
    if cfg.family != "dense":
        raise TPShardingError(
            f"tensor-parallel serving supports the dense family only, "
            f"got {cfg.family!r} ({cfg.name})")
    bad = {ax: dim for ax, dim in
           (("n_heads", cfg.n_heads), ("n_kv_heads", cfg.n_kv_heads),
            ("d_ff", cfg.d_ff), ("vocab", cfg.vocab))
           if dim % tp}
    if bad:
        raise TPShardingError(
            f"{cfg.name}: tp={tp} must divide every sharded axis; "
            f"indivisible: {bad} (kv-head replication is not offered)")


def tp_param_specs(params: dict, block_key: str = "blocks") -> dict:
    """PartitionSpec per param (arrays or shapes): column-parallel shards
    on the model axis, everything contraction-sharded in Megatron stays
    replicated here."""
    specs = {}
    for k, v in params.items():
        stacked = k.startswith(block_key + "/")
        lead = (None,) if stacked else ()
        if k.endswith("attn/wq"):
            specs[k] = P(*lead, None, MODEL, None)
        elif k.endswith(("attn/wk", "attn/wv")):
            specs[k] = P(*lead, None, MODEL, None)
        elif k.endswith(("attn/bq", "attn/bk", "attn/bv")):
            specs[k] = P(*lead, MODEL, None)
        elif k.endswith(("mlp/w_in", "mlp/w_gate")):
            specs[k] = P(*lead, None, MODEL)
        elif k == "unembed":
            specs[k] = P(None, MODEL)
        else:  # norms, embed, wo, w_out: replicated (wo/w_out consume
            #    gathered full-width activations)
            specs[k] = P(*([None] * v.ndim))
    return specs


def tp_cache_specs() -> dict:
    """KV cache [L, S, KVH, B, D] (``DenseModel.cache_shape``'s order)
    shards on the kv-head axis."""
    kv = P(None, None, MODEL, None, None)
    return {"k": kv, "v": kv, "pos": P()}


def build_tp_step_fns(model, pspecs: dict, mesh, max_seq: int):
    """(prefill, decode) callables matching ``DenseModel.prefill`` /
    ``decode_step`` signatures, tensor-parallel over ``mesh``'s model
    axis, for parameters laid out as ``pspecs`` (:func:`tp_param_specs`).
    Each device runs the model's own forward on its shards; the forward
    gathers an activation where it is narrower than the weight that
    consumes it (:func:`repro.models.layers.gather_shards`).
    Raises :class:`TPShardingError` for unshardable shapes."""
    validate_tp(model.cfg, mesh.shape[MODEL])
    cache_spec = tp_cache_specs()
    out_specs = (cache_spec, P(None, None, None))

    sm_prefill = shard_map(
        lambda p, tokens: model.prefill(p, {"tokens": tokens}, max_seq),
        mesh, in_specs=(pspecs, P(None, None)), out_specs=out_specs,
        axis_names={MODEL})
    sm_decode = shard_map(model.decode_step, mesh,
                          in_specs=(pspecs, cache_spec, P(None, None)),
                          out_specs=out_specs, axis_names={MODEL})

    # named functions, so the profile shows ``jit_prefill`` and
    # ``jit_decode_step`` as on one device
    def prefill(params, batch):
        return sm_prefill(params, batch["tokens"])

    def decode_step(params, cache, tokens):
        return sm_decode(params, cache, tokens)

    return prefill, decode_step


def _gathers(jaxpr, times: int = 1):
    """(executions a call, equation) of every all-gather in ``jaxpr`` and
    the programs nested in it; a scan's body runs ``length`` times."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "all_gather":
            yield times, eqn
        reps = times
        if eqn.primitive.name == "scan":
            reps *= eqn.params["length"]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _gathers(sub, reps)


def _counts(step, *args):
    """(counters of one call of ``step`` on ``args``, its output shapes)."""
    closed, out = jax.make_jaxpr(step, return_shape=True)(*args)
    n = received = 0
    for times, eqn in _gathers(closed.jaxpr):
        tp = eqn.params["axis_size"]
        aval = eqn.outvars[0].aval
        n += times
        # a device holds 1/tp of the gathered tensor and receives the rest
        received += times * aval.size * aval.dtype.itemsize * (tp - 1) // tp
    return {"collectives": n, "exchange_bytes": received}, out


def exchange_counts(prefill, decode, params, batch) -> tuple[dict, dict]:
    """Counters of the exchange for one ``prefill`` call on ``batch`` and
    one ``decode`` step after it (:func:`build_tp_step_fns`' pair):
    ``collectives``, the all-gathers the call makes (2 a layer and one at
    the logits), and ``exchange_bytes``, the bytes one device receives in
    them. Read from the steps' traced programs, so they follow the shapes
    each step gathers; nothing is compiled or run."""
    pre, (cache, logits) = _counts(prefill, params, batch)
    tokens = jax.ShapeDtypeStruct((logits.shape[0], 1), jnp.int32)
    dec, _ = _counts(decode, params, cache, tokens)
    return pre, dec
