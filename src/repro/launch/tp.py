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
from repro.models.layers import attention, rmsnorm, rope

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
    """KV cache [L, B, S, KVH, D] shards on the kv-head axis."""
    kv = P(None, None, None, MODEL, None)
    return {"k": kv, "v": kv, "pos": P()}


def _tp_forward(cfg, block_key: str):
    """Per-device worker: the DenseModel forward with gathers at the two
    contraction-sharded matmuls (attention out-proj, MLP down-proj) and
    at the logits. Mirrors transformer.apply_block exactly elsewhere."""
    eps, theta = cfg.eps, cfg.rope_theta

    def fwd(p, cache, tokens, last_only):
        x = p["embed"][tokens].astype(cfg.cdtype)
        pos0 = cache["pos"]
        positions = pos0 + jnp.arange(x.shape[1])
        pre = block_key + "/"
        blocks = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}

        def body(h, xs):
            lp, k_l, v_l = xs
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
            kc = jax.lax.dynamic_update_slice(k_l, k.astype(k_l.dtype),
                                              (0, pos0, 0, 0))
            vc = jax.lax.dynamic_update_slice(v_l, v.astype(v_l.dtype),
                                              (0, pos0, 0, 0))
            out = attention(q, kc, vc, causal=True, q_offset=pos0)
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
            h = h + hid @ lp["mlp/w_out"]
            return h, (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x, (blocks, cache["k"], cache["v"]))
        new_cache = {"k": ks, "v": vs, "pos": pos0 + tokens.shape[1]}
        if last_only:
            x = x[:, -1:]
        x = rmsnorm(x, p["ln_f"], eps)
        logits = x @ p["unembed"]
        logits = jax.lax.all_gather(logits, MODEL, axis=2, tiled=True)
        return new_cache, logits.astype(jnp.float32)

    return fwd


def build_tp_step_fns(model, pspecs: dict, mesh, max_seq: int):
    """(prefill, decode) callables matching ``DenseModel.prefill`` /
    ``decode_step`` signatures, tensor-parallel over ``mesh``'s model
    axis, for parameters laid out as ``pspecs`` (:func:`tp_param_specs`).
    Raises :class:`TPShardingError` for unshardable shapes."""
    cfg = model.cfg
    tp = mesh.shape[MODEL]
    validate_tp(cfg, tp)
    fwd = _tp_forward(cfg, model.block_key)
    cache_spec = tp_cache_specs()
    out_specs = (cache_spec, P(None, None, None))
    kvh_local = cfg.n_kv_heads // tp

    def prefill_worker(p, tokens):
        b = tokens.shape[0]
        shape = (cfg.n_layers, b, max_seq, kvh_local, cfg.hd)
        cache = {"k": jnp.zeros(shape, cfg.pdtype),
                 "v": jnp.zeros(shape, cfg.pdtype),
                 "pos": jnp.asarray(0, jnp.int32)}
        return fwd(p, cache, tokens, True)

    sm_prefill = shard_map(prefill_worker, mesh,
                           in_specs=(pspecs, P(None, None)),
                           out_specs=out_specs, axis_names={MODEL})
    sm_decode = shard_map(lambda p, c, t: fwd(p, c, t, False), mesh,
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
