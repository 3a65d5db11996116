"""Dense decoder-only transformer (starcoder2 / yi / minitron / qwen2.5)
plus the VLM variant (internvl2: same backbone, patch-embedding stub).

Layer-stacked parameters + lax.scan over layers keep the HLO compact for
the 512-device dry-run; single-block probe entry points give the roofline
exact per-layer costs (XLA's cost analysis counts a while body once).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamBuilder, Rules, flat_get, stack_init, shard_act, remat_policy
from .config import ModelConfig
from .layers import (apply_attn, cross_entropy, gather_shards, init_attn,
                     init_mlp, init_norm, mlp, prefill_cache, rmsnorm)

__all__ = ["DenseModel", "init_block", "apply_block"]


def init_block(cfg: ModelConfig, rules: Rules):
    """Builder for one decoder block's params (flat dict + specs)."""

    def build(key):
        b = ParamBuilder(key, cfg.pdtype)
        init_norm(b, "ln1", cfg.d_model)
        init_attn(b, cfg, rules)
        init_norm(b, "ln2", cfg.d_model)
        init_mlp(b, cfg, rules)
        return b.params, b.specs

    return build


def apply_block(p: dict, cfg: ModelConfig, x, *, positions, cache=None,
                layer=None, q_chunk=None, act_spec=None, window=None,
                rules=None):
    """Pre-norm block: x + attn(ln(x)); x + mlp(ln(x)). Returns (x, kv),
    ``kv`` as :func:`~repro.models.layers.apply_attn` returns it."""
    h, new_cache = apply_attn(p, cfg, rmsnorm(x, p["ln1"], cfg.eps),
                              positions=positions, cache=cache, layer=layer,
                              q_chunk=q_chunk, window=window)
    x = shard_act(x + h, act_spec, rules)
    x = x + mlp(p, cfg, rmsnorm(x, p["ln2"], cfg.eps))
    return shard_act(x, act_spec, rules), new_cache


class DenseModel:
    """family in {"dense", "vlm"}."""

    block_key = "blocks"

    def __init__(self, cfg: ModelConfig, rules: Rules | None = None,
                 seq_shard: bool = True):
        self.cfg = cfg
        self.rules = rules or Rules({})
        # sequence-parallel layer-boundary activations (hillclimb lever)
        mdl = self.rules.present("model")
        self.act_spec = P(self.rules.dp() or None,
                          mdl[0] if (seq_shard and mdl) else None, None)

    # ------------------------------------------------------------- params
    def _build_block(self):
        return init_block(self.cfg, self.rules)

    def init(self, key):
        cfg, rules = self.cfg, self.rules
        kb, ke, ku, kf = jax.random.split(key, 4)
        params, specs = stack_init(self._build_block(), kb, cfg.n_layers)
        params = {f"{self.block_key}/{k}": v for k, v in params.items()}
        specs = {f"{self.block_key}/{k}": v for k, v in specs.items()}
        b = ParamBuilder(ke, cfg.pdtype)
        vocab_sh = rules.maybe(cfg.vocab, "model")
        d_sh = rules.maybe(cfg.d_model, "data")
        b.normal("embed", (cfg.vocab, cfg.d_model), P(vocab_sh, d_sh), scale=1.0)
        b.normal("unembed", (cfg.d_model, cfg.vocab), P(d_sh, vocab_sh))
        init_norm(b, "ln_f", cfg.d_model)
        if cfg.family == "vlm":
            # patch-embedding stub: a projection of precomputed ViT features
            b.normal("vision_proj", (cfg.d_model, cfg.d_model), P(d_sh, None))
        params.update(b.params)
        specs.update(b.specs)
        self._specs = specs
        return params

    def abstract(self, key=None):
        """(shapes, specs) without allocating — dry-run entry."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return shapes, dict(self._specs)

    # ------------------------------------------------------------ forward
    def embed_inputs(self, params, batch):
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(cfg.cdtype)
        if cfg.family == "vlm":
            vis = batch["vision"].astype(cfg.cdtype) @ params["vision_proj"]
            x = jnp.concatenate([vis, x], axis=1)
        return shard_act(x, self.act_spec, self.rules)

    def _apply_block(self, p, x, *, positions, cache=None, layer=None,
                     q_chunk=None, window=None):
        """One decoder block of this family (MoE overrides it)."""
        return apply_block(p, self.cfg, x, positions=positions, cache=cache,
                           layer=layer, q_chunk=q_chunk,
                           act_spec=self.act_spec, window=window,
                           rules=self.rules)

    def _scan_blocks(self, params, x, positions, q_chunk, window=None):
        blocks = flat_get(params, self.block_key)

        def body(h, layer_p):
            h, _ = self._apply_block(layer_p, h, positions=positions,
                                     q_chunk=q_chunk, window=window)
            return h, None

        body = jax.checkpoint(body, policy=remat_policy())
        x, _ = jax.lax.scan(body, x, blocks)
        return x

    def hidden_states(self, params, batch, q_chunk=None):
        x = self.embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        return self._scan_blocks(params, x, positions, q_chunk)

    def loss(self, params, batch, q_chunk=None, loss_chunk=512):
        """Next-token CE. For VLM, loss is only on the text positions."""
        cfg = self.cfg
        x = self.hidden_states(params, batch, q_chunk=q_chunk)
        x = rmsnorm(x, params["ln_f"], cfg.eps)
        tokens = batch["tokens"]
        n_front = x.shape[1] - tokens.shape[1]
        x_text = x[:, n_front:]
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        return cross_entropy(lambda l: l, x_text, params["unembed"], labels,
                             mask=mask, chunk=loss_chunk)

    # ------------------------------------------------------------ serving
    def cache_shape(self, batch_size: int, max_seq: int):
        """k/v [L, max_seq, KVH, B, D]: the batch second-minor, the order
        in which the decode step updates the cache in place
        (:func:`~repro.models.layers.append_kv`)."""
        cfg = self.cfg
        kvh_sh = self.rules.maybe(cfg.n_kv_heads, "model")
        seq_sh = self.rules.maybe(max_seq, "model") if kvh_sh is None else None
        bsp = self.rules.maybe(batch_size, "pod", "data")
        spec = P(None, seq_sh, kvh_sh, bsp, None)
        shape = (cfg.n_layers, max_seq, cfg.n_kv_heads, batch_size, cfg.hd)
        return {"k": (shape, spec), "v": (shape, spec), "pos": ((), P())}

    def init_cache(self, batch_size: int, max_seq: int):
        shapes = self.cache_shape(batch_size, max_seq)
        cache = {k: jnp.zeros(s, self.cfg.pdtype if k != "pos" else jnp.int32)
                 for k, (s, _) in shapes.items()}
        cache["pos"] = jnp.asarray(0, jnp.int32)
        return cache

    def cache_specs(self, batch_size: int, max_seq: int):
        return {k: spec for k, (_, spec) in self.cache_shape(batch_size, max_seq).items()}

    def prefill(self, params, batch, max_seq: int, q_chunk=None,
                n_logits: int = 1):
        """-> (cache, logits of the last ``n_logits`` positions [B, n, V]).
        Each layer attends over the prompt's own keys and values; the
        cache is written from them once, padded to ``max_seq``."""
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])

        def body(h, layer_p):
            h, kv = self._apply_block(layer_p, h, positions=positions,
                                      q_chunk=q_chunk)
            return h, (kv["k"], kv["v"])

        x, (ks, vs) = jax.lax.scan(body, x, flat_get(params, self.block_key))
        cache = prefill_cache(ks, vs, max_seq, cfg.pdtype)
        return cache, self._logits(params, x[:, -n_logits:])

    def decode_step(self, params, cache, tokens):
        """tokens [B, 1] -> (new_cache, logits [B, 1, V]). The cache rides
        in the layer scan's carry; each layer writes its new position in
        place and attends over its own slice."""
        cfg = self.cfg
        x = params["embed"][tokens].astype(cfg.cdtype)
        pos = cache["pos"]
        positions = pos + jnp.arange(x.shape[1])

        def body(carry, xs):
            h, k, v = carry
            layer, layer_p = xs
            h, c = self._apply_block(layer_p, h, positions=positions,
                                     cache={"k": k, "v": v, "pos": pos},
                                     layer=layer)
            return (h, c["k"], c["v"]), None

        (x, k, v), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (jnp.arange(cfg.n_layers), flat_get(params, self.block_key)))
        cache = {"k": k, "v": v, "pos": pos + x.shape[1]}
        return cache, self._logits(params, x)

    def _logits(self, params, x):
        """Final norm and unembedding of hidden states ``x``, as float32
        logits over the whole vocabulary (gathered inside the
        tensor-parallel step, whose unembedding is vocab-sharded)."""
        x = rmsnorm(x, params["ln_f"], self.cfg.eps)
        logits = gather_shards(x @ params["unembed"], x.ndim - 1,
                               self.cfg.vocab)
        return logits.astype(jnp.float32)

    # ------------------------------------------------------------- probes
    def probe_block(self):
        """(fn, multiplier): one decoder block, for exact per-layer costs."""

        def fn(layer_p, x):
            positions = jnp.arange(x.shape[1])
            y, _ = self._apply_block(layer_p, x, positions=positions)
            return y

        return fn, self.cfg.n_layers

    def probe_block_decode(self):
        """(fn, multiplier): one decoder block's decode step over one
        layer's cache k/v [B, Smax, KVH, D]."""

        def fn(layer_p, x, k, v, pos):
            positions = pos + jnp.arange(x.shape[1])
            y, c = self._apply_block(layer_p, x, positions=positions,
                                     cache={"k": k, "v": v, "pos": pos})
            return y, c["k"], c["v"]

        return fn, self.cfg.n_layers
