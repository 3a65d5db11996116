"""Shared neural layers: RMSNorm, RoPE, GQA attention, MLP, embeddings.

All functions are pure and operate on flat param sub-dicts. Attention
supports full, causal, sliding-window and query-chunked evaluation, and a
single code path serves train, prefill and decode (q_offset shifts the
causal mask for cached decoding).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import MODEL, ParamBuilder, Rules

__all__ = ["rmsnorm", "rope", "attention", "mlp", "init_attn", "init_mlp",
           "cross_entropy", "apply_attn", "init_norm", "append_kv",
           "prefill_cache", "gather_shards"]


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(dt)


def init_norm(b: ParamBuilder, name: str, d: int) -> None:
    b.ones(name, (d,), P(None))


def gather_shards(x: jnp.ndarray, axis: int, width: int) -> jnp.ndarray:
    """``x`` at its full ``width`` along ``axis``, the width of the weight
    that consumes it. Narrower, ``x`` is one device's shard inside the
    tensor-parallel step's ``shard_map`` (:mod:`repro.launch.tp`), and
    the shards are all-gathered on the model axis in axis order, so the
    result is elementwise the dense layout. Elsewhere shapes are global
    and nothing is emitted."""
    if x.shape[axis] >= width:
        return x
    return jax.lax.all_gather(x, MODEL, axis=axis, tiled=True)


# ------------------------------------------------------------------- RoPE

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [S] or [B, S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[None, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                      # [1, S, 1, half]
    else:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                      # [B, S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention

def _attend_block(q, k, v, q_pos, k_pos, causal, window):
    """q [B,Sq,KV,G,D], k/v [B,Skv,KV,D] -> [B,Sq,KV,G,D]; f32 softmax."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out


def attention(q, k, v, *, causal: bool = True, q_offset=0,
              window: int | None = None, q_chunk: int | None = None,
              unroll: bool = False):
    """Grouped-query attention.

    q: [B, Sq, H, D]; k, v: [B, Skv, KVH, D]. H % KVH == 0.
    ``q_offset``: absolute position of q[0] (for cached decode; may be a
    traced scalar). ``q_chunk``: evaluate queries in chunks of this size so
    the [Sq, Skv] score matrix never fully materialises (the memory-
    feasibility knob for 32k prefill). Chunks run under ``lax.map`` by
    default (one chunk's buffers live at a time); ``unroll=True`` emits
    static per-chunk HLO instead — used by the roofline probes, whose cost
    analysis cannot see through a while loop. With ``window`` set and a
    static offset, chunks use *banded* key slices: FLOPs scale with
    Sq x (window + chunk), not Sq x Skv.
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    k_pos = jnp.arange(k.shape[1])
    q_pos = jnp.asarray(q_offset) + jnp.arange(sq)
    if q_chunk is None or sq <= q_chunk:
        out = _attend_block(qg, k, v, q_pos, k_pos, causal, window)
        return out.reshape(b, sq, h, d)

    assert sq % q_chunk == 0, (sq, q_chunk)
    n_chunks = sq // q_chunk
    static_offset = isinstance(q_offset, int)
    banded = window is not None and static_offset and sq == k.shape[1]

    if banded:
        # left-pad keys by `window` so every chunk sees a uniform
        # (window + q_chunk)-wide band at an affine offset
        pad = ((0, 0), (window, 0), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)
        kp_pos = jnp.concatenate([jnp.full((window,), -(10**9)), k_pos])

        def chunk(c):
            qs = jax.lax.dynamic_slice_in_dim(qg, c * q_chunk, q_chunk, 1)
            ks = jax.lax.dynamic_slice_in_dim(kp, c * q_chunk,
                                              window + q_chunk, 1)
            vs = jax.lax.dynamic_slice_in_dim(vp, c * q_chunk,
                                              window + q_chunk, 1)
            ps = jax.lax.dynamic_slice_in_dim(kp_pos, c * q_chunk,
                                              window + q_chunk, 0)
            qp = jax.lax.dynamic_slice_in_dim(q_pos, c * q_chunk, q_chunk, 0)
            return _attend_block(qs, ks, vs, qp, ps, causal, window)
    else:
        def chunk(c):
            qs = jax.lax.dynamic_slice_in_dim(qg, c * q_chunk, q_chunk, 1)
            qp = jax.lax.dynamic_slice_in_dim(q_pos, c * q_chunk, q_chunk, 0)
            return _attend_block(qs, k, v, qp, k_pos, causal, window)

    if unroll:
        out = jnp.concatenate([chunk(c) for c in range(n_chunks)], axis=1)
    else:
        ys = jax.lax.map(chunk, jnp.arange(n_chunks))   # [n, B, qc, ...]
        out = jnp.moveaxis(ys, 0, 1).reshape(b, sq, kvh, g, d)
    return out.reshape(b, sq, h, d)


def init_attn(b: ParamBuilder, cfg, rules: Rules, prefix: str = "attn") -> None:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dp, mdl = rules.maybe(d, "data"), rules.maybe(h, "model")
    kv_mdl = rules.maybe(kvh, "model")
    b.normal(f"{prefix}/wq", (d, h, hd), P(dp, mdl, None))
    b.normal(f"{prefix}/wk", (d, kvh, hd), P(dp, kv_mdl, None))
    b.normal(f"{prefix}/wv", (d, kvh, hd), P(dp, kv_mdl, None))
    b.normal(f"{prefix}/wo", (h, hd, d), P(mdl, None, dp),
             scale=1.0 / math.sqrt(h * hd))
    if cfg.qkv_bias:
        b.zeros(f"{prefix}/bq", (h, hd), P(mdl, None))
        b.zeros(f"{prefix}/bk", (kvh, hd), P(kv_mdl, None))
        b.zeros(f"{prefix}/bv", (kvh, hd), P(kv_mdl, None))


def _stored(kv: jnp.ndarray) -> jnp.ndarray:
    """[B, S, KVH, D] -> [S, KVH, B, D], one layer of the stacked cache."""
    return jnp.transpose(kv, (1, 2, 0, 3))


def append_kv(cache: dict, k: jnp.ndarray, v: jnp.ndarray, layer=None):
    """Write a call's keys and values ``k``, ``v`` [B, S, KVH, D] into
    ``cache`` at ``cache["pos"]``; returns (the updated cache, the keys and
    values the call attends over, [B, Smax, KVH, D]).

    ``cache`` holds one layer's k/v [B, Smax, KVH, D] when ``layer`` is
    None. Otherwise it is the serving cache of every layer, k/v
    [L, Smax, KVH, B, D] (batch second-minor, the order the compiler lays
    the decode step's carry out in), and only the S new positions of
    ``layer`` are written: a donated cache is then updated in place.
    """
    pos = cache["pos"]
    if layer is None:
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, pos, 0, 0))
        k_att, v_att = ck, cv
    else:
        at = (layer, pos, 0, 0, 0)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], _stored(k).astype(cache["k"].dtype)[None], at)
        cv = jax.lax.dynamic_update_slice(
            cache["v"], _stored(v).astype(cache["v"].dtype)[None], at)
        k_att = jnp.transpose(ck[layer], (2, 0, 1, 3))
        v_att = jnp.transpose(cv[layer], (2, 0, 1, 3))
    return {"k": ck, "v": cv, "pos": pos + k.shape[1]}, k_att, v_att


def prefill_cache(ks: jnp.ndarray, vs: jnp.ndarray, max_seq: int, dtype):
    """The serving cache (:func:`append_kv`'s stacked order) of a prefill
    from its layers' own keys and values ``ks``, ``vs`` [L, B, S, KVH, D]:
    written once, padded to ``max_seq`` positions, at position S."""
    s = ks.shape[2]
    pad = ((0, 0), (0, max_seq - s), (0, 0), (0, 0), (0, 0))

    def whole(x):
        return jnp.pad(jnp.transpose(x, (0, 2, 3, 1, 4)).astype(dtype), pad)

    return {"k": whole(ks), "v": whole(vs), "pos": jnp.asarray(s, jnp.int32)}


def apply_attn(p: dict, cfg, x: jnp.ndarray, *, positions, cache=None,
               layer=None, window: int | None = None,
               q_chunk: int | None = None, prefix: str = "attn",
               kv_override=None, use_rope: bool = True, unroll: bool = False):
    """Full attention sub-block: qkv proj -> rope -> (cache) -> attn -> out.

    cache: None (training/prefill: attend over the call's own keys) or a
    dict {"k", "v", "pos"} that decode appends to at ``pos`` and attends
    over the first pos+Sq entries of: one layer's [B, Smax, KVH, D], or
    with ``layer`` set the stacked serving cache (:func:`append_kv`).
    kv_override: (k, v) for cross-attention (keys from the encoder).
    Returns (out, kv): with a cache, the updated cache; without one, the
    call's own keys and values {"k", "v"} [B, S, KVH, D] (what a prefill
    stores).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p[f"{prefix}/wq"])
    if f"{prefix}/bq" in p:
        q = q + p[f"{prefix}/bq"]
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p[f"{prefix}/wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p[f"{prefix}/wv"])
        if f"{prefix}/bk" in p:
            k = k + p[f"{prefix}/bk"]
            v = v + p[f"{prefix}/bv"]
        if use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        causal = True
    else:
        k, v = kv_override
        if use_rope:
            q = rope(q, positions, cfg.rope_theta)
        causal = False

    kv = {"k": k, "v": v}
    q_offset = 0
    if cache is not None:
        q_offset = cache["pos"]
        kv, k, v = append_kv(cache, k, v, layer)

    out = attention(q, k, v, causal=causal, q_offset=q_offset,
                    window=window, q_chunk=q_chunk, unroll=unroll)
    # mask out not-yet-written cache slots is handled by the causal mask
    # (q_offset bounds the attended range).
    wo = p[f"{prefix}/wo"]
    y = jnp.einsum("bshk,hkd->bsd", gather_shards(out, 2, wo.shape[0]), wo)
    return y, kv


# -------------------------------------------------------------------- MLP

def init_mlp(b: ParamBuilder, cfg, rules: Rules, prefix: str = "mlp",
             d_ff: int | None = None) -> None:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dp, mdl = rules.maybe(d, "data"), rules.maybe(f, "model")
    if cfg.mlp_variant in ("swiglu", "geglu"):
        b.normal(f"{prefix}/w_gate", (d, f), P(dp, mdl))
    b.normal(f"{prefix}/w_in", (d, f), P(dp, mdl))
    b.normal(f"{prefix}/w_out", (f, d), P(mdl, dp))


def mlp(p: dict, cfg, x: jnp.ndarray, prefix: str = "mlp") -> jnp.ndarray:
    h = x @ p[f"{prefix}/w_in"]
    if cfg.mlp_variant == "swiglu":
        h = jax.nn.silu(x @ p[f"{prefix}/w_gate"]) * h
    elif cfg.mlp_variant == "geglu":
        h = jax.nn.gelu(x @ p[f"{prefix}/w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    w_out = p[f"{prefix}/w_out"]
    return gather_shards(h, h.ndim - 1, w_out.shape[0]) @ w_out


# ---------------------------------------------------------- loss / logits

def cross_entropy(logits_fn, x: jnp.ndarray, unembed: jnp.ndarray,
                  labels: jnp.ndarray, mask: jnp.ndarray | None = None,
                  chunk: int = 512):
    """Chunked next-token cross-entropy.

    ``x`` [B, S, D] final hidden states; ``unembed`` [D, V]; ``labels``
    [B, S]. The [B, chunk, V] logits are materialised one sequence-chunk
    at a time (python-unrolled: exact HLO flops, bounded memory even at
    V=256k). logits_fn lets callers post-process logits (e.g. cap/scale).
    """
    b, s, _ = x.shape
    chunk = min(chunk, s)
    while s % chunk:  # snap to the largest divisor of s not above chunk
        chunk -= 1
    total = jnp.float32(0.0)
    count = jnp.float32(0.0)
    for c in range(s // chunk):
        sl = slice(c * chunk, (c + 1) * chunk)
        logits = logits_fn(x[:, sl] @ unembed).astype(jnp.float32)  # [B,c,V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = labels[:, sl]
        picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = lse - picked
        m = jnp.ones_like(nll) if mask is None else mask[:, sl].astype(jnp.float32)
        total = total + (nll * m).sum()
        count = count + m.sum()
    return total / jnp.maximum(count, 1.0)
