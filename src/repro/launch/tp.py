"""Tensor-parallel serving step functions (gather-based).

The sharded path must compute the single-device engine's logits — it is
the same platform quoted at a different mesh shape, and the allocator's
accountability story dies the moment "same work, wider mesh" changes the
answer. psum-based (Megatron-style row-parallel) output projections split
each contraction across devices and add the partial sums; this module
instead shards only *column-parallel* weights (q/k/v heads, MLP hidden,
unembed vocab) and **all-gathers activations** back to full width before
every contraction-sharded matmul, which then runs replicated.
``all_gather(tiled=True)`` concatenates shards in axis order, so gathered
tensors are elementwise identical to their dense layout and every op
computes the same per-element arithmetic as the dense path.

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
from repro.models.layers import (append_kv, attention, prefill_cache,
                                 rmsnorm, rope)

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


def _tp_forward(cfg, block_key: str, max_seq: int):
    """Per-device workers (prefill, decode): the DenseModel forward with
    gathers at the two contraction-sharded matmuls (attention out-proj,
    MLP down-proj) and at the logits. Mirrors transformer.apply_block
    exactly elsewhere; the cache goes as in ``DenseModel.prefill`` and
    ``decode_step``."""
    eps, theta = cfg.eps, cfg.rope_theta
    pre = block_key + "/"

    def layer(lp, h, positions, attend):
        """One block; ``attend(q, k, v)`` -> (attention output, what the
        layer keeps of the cache)."""
        xn = rmsnorm(h, lp["ln1"], eps)
        q = jnp.einsum("bsd,dhk->bshk", xn, lp["attn/wq"])
        k = jnp.einsum("bsd,dhk->bshk", xn, lp["attn/wk"])
        v = jnp.einsum("bsd,dhk->bshk", xn, lp["attn/wv"])
        if "attn/bq" in lp:
            q = q + lp["attn/bq"]
            k = k + lp["attn/bk"]
            v = v + lp["attn/bv"]
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
        out, kept = attend(q, k, v)
        out = jax.lax.all_gather(out, MODEL, axis=2, tiled=True)
        h = h + jnp.einsum("bshk,hkd->bsd", out, lp["attn/wo"])
        xn = rmsnorm(h, lp["ln2"], eps)
        hid = xn @ lp["mlp/w_in"]
        if cfg.mlp_variant == "swiglu":
            hid = jax.nn.silu(xn @ lp["mlp/w_gate"]) * hid
        elif cfg.mlp_variant == "geglu":
            hid = jax.nn.gelu(xn @ lp["mlp/w_gate"]) * hid
        else:
            hid = jax.nn.gelu(hid)
        hid = jax.lax.all_gather(hid, MODEL, axis=2, tiled=True)
        return h + hid @ lp["mlp/w_out"], kept

    def logits(p, x):
        x = rmsnorm(x, p["ln_f"], eps)
        out = jax.lax.all_gather(x @ p["unembed"], MODEL, axis=2, tiled=True)
        return out.astype(jnp.float32)

    def blocks(p):
        return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}

    def prefill(p, tokens):
        x = p["embed"][tokens].astype(cfg.cdtype)
        positions = jnp.arange(x.shape[1])

        def attend(q, k, v):
            return attention(q, k, v, causal=True), (k, v)

        def body(h, lp):
            return layer(lp, h, positions, attend)

        x, (ks, vs) = jax.lax.scan(body, x, blocks(p))
        return (prefill_cache(ks, vs, max_seq, cfg.pdtype),
                logits(p, x[:, -1:]))

    def decode(p, cache, tokens):
        x = p["embed"][tokens].astype(cfg.cdtype)
        pos0 = cache["pos"]
        positions = pos0 + jnp.arange(x.shape[1])

        def body(carry, xs):
            h, kc, vc = carry
            i, lp = xs

            def attend(q, k, v):
                c, k, v = append_kv({"k": kc, "v": vc, "pos": pos0}, k, v, i)
                return (attention(q, k, v, causal=True, q_offset=pos0),
                        (c["k"], c["v"]))

            h, (kc, vc) = layer(lp, h, positions, attend)
            return (h, kc, vc), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (jnp.arange(cfg.n_layers), blocks(p)))
        return ({"k": ks, "v": vs, "pos": pos0 + tokens.shape[1]},
                logits(p, x))

    return prefill, decode


def build_tp_step_fns(model, pspecs: dict, mesh, max_seq: int):
    """(prefill, decode) callables matching ``DenseModel.prefill`` /
    ``decode_step`` signatures, tensor-parallel over ``mesh``'s model
    axis, for parameters laid out as ``pspecs`` (:func:`tp_param_specs`).
    Raises :class:`TPShardingError` for unshardable shapes."""
    cfg = model.cfg
    tp = mesh.shape[MODEL]
    validate_tp(cfg, tp)
    prefill_worker, decode_worker = _tp_forward(cfg, model.block_key, max_seq)
    cache_spec = tp_cache_specs()
    out_specs = (cache_spec, P(None, None, None))

    sm_prefill = shard_map(prefill_worker, mesh,
                           in_specs=(pspecs, P(None, None)),
                           out_specs=out_specs, axis_names={MODEL})
    sm_decode = shard_map(decode_worker, mesh,
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
