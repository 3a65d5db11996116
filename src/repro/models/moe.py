"""Mixture-of-Experts decoder (moonshot 64e top-6; arctic 128e top-2 +
dense residual).

Dispatch is scatter/gather-based, NOT one-hot-einsum-based: the GShard
dispatch einsum inflates HLO FLOPs by O(E*C/k) (~100x here), which would
poison the roofline's compute term. Instead each (token, k) copy computes
its position inside its expert's capacity buffer with a cumsum rank, is
scatter-added into the [B, E, C, D] buffer, processed by the batched
expert matmul (the only real FLOPs), and gathered back. Tokens beyond an
expert's capacity are dropped (standard capacity-factor semantics).

Expert weights are sharded over the "model" axis (expert parallelism);
the buffer is sharded [B->data, E->model], so dispatch/return traffic
shows up as the collective term the paper's gamma would model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamBuilder, Rules, shard_act
from .config import ModelConfig
from .layers import apply_attn, init_attn, init_mlp, init_norm, mlp, rmsnorm
from .transformer import DenseModel

__all__ = ["MoEModel", "moe_ffn", "init_moe_ffn"]


def init_moe_ffn(b: ParamBuilder, cfg: ModelConfig, rules: Rules,
                 prefix: str = "moe") -> None:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ep = rules.maybe(e, "model")
    dp = rules.maybe(d, "data")
    b.normal(f"{prefix}/router", (d, e), P(dp, None))
    b.normal(f"{prefix}/w_gate", (e, d, f), P(ep, dp, None))
    b.normal(f"{prefix}/w_in", (e, d, f), P(ep, dp, None))
    b.normal(f"{prefix}/w_out", (e, f, d), P(ep, None, dp))


#: "scatter" — baseline: the dispatch scatter writes straight into the
#: expert-sharded buffer (GSPMD resolves the sharded scatter with gathers).
#: "a2a" — beyond-paper optimisation (§Perf iteration 1): the scatter stays
#: local to the token (data) sharding and ONE explicit reshard moves the
#: buffer to expert (model) sharding — the classic MoE all-to-all expressed
#: as a sharding-constraint pair.
DISPATCH_MODE = "scatter"


def moe_ffn(p: dict, cfg: ModelConfig, x: jnp.ndarray, rules: Rules,
            prefix: str = "moe", dispatch: str | None = None) -> jnp.ndarray:
    """x: [B, S, D] -> [B, S, D]. Capacity is per sequence (group = batch
    row), so rank cumsums stay local to the unsharded sequence dim."""
    dispatch = dispatch or DISPATCH_MODE
    if x.shape[1] == 1 and x.shape[0] > 1:
        # decode: per-sequence capacity wastes ~E*C/k slots per token —
        # use ONE batch-global group (buf [E, C, D] is tiny)
        return _moe_ffn_decode(p, cfg, x, rules, prefix)
    if dispatch == "a2a_sp":
        return _moe_ffn_sp(p, cfg, x, rules, prefix)
    bsz, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(int(math.ceil(s * k * cfg.capacity_factor / e)), 4)
    dp = rules.dp() or None
    ep = rules.maybe(e, "model")
    token_spec = P(dp, None, None, None)
    expert_spec = P(dp, ep, None, None)

    scores = (x @ p[f"{prefix}/router"]).astype(jnp.float32)      # [B,S,E]
    gates = jax.nn.softmax(scores, axis=-1)
    topw, topi = jax.lax.top_k(gates, k)                          # [B,S,K]
    topw = (topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    # rank of each (token, k) copy within its expert, per sequence
    flat_i = topi.reshape(bsz, s * k)                             # [B, S*K]
    onehot = jax.nn.one_hot(flat_i, e, dtype=jnp.int32)           # [B, S*K, E]
    ranks_all = jnp.cumsum(onehot, axis=1) - onehot               # rank if chosen
    rank = jnp.take_along_axis(ranks_all, flat_i[..., None], axis=-1)[..., 0]
    keep = (rank < cap)                                           # capacity drop
    rank_c = jnp.minimum(rank, cap - 1)

    # scatter token copies into the expert buffer [B, E, C, D]
    bidx = jnp.broadcast_to(jnp.arange(bsz)[:, None], flat_i.shape)
    updates = jnp.repeat(x, k, axis=1) * keep[..., None].astype(x.dtype)
    buf = jnp.zeros((bsz, e, cap, d), x.dtype)
    if dispatch == "a2a":
        # keep the scatter local to the token sharding...
        buf = shard_act(buf, token_spec, rules)
        buf = buf.at[bidx, flat_i, rank_c].add(updates)
        buf = shard_act(buf, token_spec, rules)
        # ...then pay ONE explicit reshard to expert sharding (the a2a)
        buf = shard_act(buf, expert_spec, rules)
    else:
        buf = buf.at[bidx, flat_i, rank_c].add(updates)
        buf = shard_act(buf, expert_spec, rules)

    # the real compute: batched expert matmuls [B,E,C,D] x [E,D,F]
    h = jnp.einsum("becd,edf->becf", buf, p[f"{prefix}/w_in"])
    if cfg.mlp_variant == "swiglu":
        g = jnp.einsum("becd,edf->becf", buf, p[f"{prefix}/w_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("becf,efd->becd", h, p[f"{prefix}/w_out"])

    if dispatch == "a2a":
        # reshard back so the return gather is token-local
        y = shard_act(y, token_spec, rules)

    # gather copies back and combine with gate weights
    out = y[bidx, flat_i, rank_c]                                 # [B, S*K, D]
    out = out * (topw.reshape(bsz, s * k) * keep.astype(x.dtype))[..., None]
    return out.reshape(bsz, s, k, d).sum(axis=2)


def _moe_ffn_decode(p: dict, cfg: ModelConfig, x: jnp.ndarray, rules: Rules,
                    prefix: str = "moe") -> jnp.ndarray:
    """§Perf iteration (decode cells): batch-global dispatch group.

    At S=1 the per-sequence capacity layout allocates B x E x C slots for
    B x k token copies (~255x padding for arctic) and its gathers dominate
    the decode collectives. Treating the whole batch as one group shrinks
    the buffer to [E, C, D] with C = ceil(B*k*cf/E) — a few MB — at the
    cost of a batch-wide (still tiny) rank cumsum."""
    bsz, s, d = x.shape
    assert s == 1
    e, k = cfg.n_experts, cfg.top_k
    # 2x the train capacity factor: collisions across the whole batch
    # are the only drop source at decode and the buffer is tiny anyway
    cap = max(int(math.ceil(bsz * k * 2 * cfg.capacity_factor / e)), 4)
    ep = rules.maybe(e, "model")

    xt = x[:, 0]                                               # [B, D]
    scores = (xt @ p[f"{prefix}/router"]).astype(jnp.float32)  # [B, E]
    gates = jax.nn.softmax(scores, axis=-1)
    topw, topi = jax.lax.top_k(gates, k)                       # [B, K]
    topw = (topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    flat_i = topi.reshape(bsz * k)                             # [N]
    onehot = jax.nn.one_hot(flat_i, e, dtype=jnp.int32)
    ranks_all = jnp.cumsum(onehot, axis=0) - onehot
    rank = jnp.take_along_axis(ranks_all, flat_i[:, None], axis=-1)[:, 0]
    keep = rank < cap
    rank_c = jnp.minimum(rank, cap - 1)

    updates = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[flat_i, rank_c].add(updates)
    buf = shard_act(buf, P(ep, None, None), rules)

    h = jnp.einsum("ecd,edf->ecf", buf, p[f"{prefix}/w_in"])
    if cfg.mlp_variant == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, p[f"{prefix}/w_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, p[f"{prefix}/w_out"])

    out = y[flat_i, rank_c]                                    # [N, D]
    out = out * (topw.reshape(bsz * k) * keep.astype(x.dtype))[:, None]
    return out.reshape(bsz, k, d).sum(axis=1)[:, None]


def _moe_ffn_sp(p: dict, cfg: ModelConfig, x: jnp.ndarray, rules: Rules,
                prefix: str = "moe") -> jnp.ndarray:
    """§Perf iteration 2: SP-aligned dispatch.

    Tokens arrive sequence-sharded over "model" (SP). Grouping the
    dispatch by (batch, SP shard) makes the routing cumsum AND the
    capacity scatter fully local — the only cross-device traffic left is
    the single buffer reshard [B, G, E, C', D]: G("model")->E("model"),
    i.e. a true all-to-all of exactly the dispatched activations. Capacity
    becomes per-(sequence, SP-block) — same expected drop rate, locality
    bounded (documented semantic change vs the per-sequence baseline).
    """
    bsz, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    dp = rules.dp() or None
    mdl = rules.maybe(s, "model")
    g = rules.axis_sizes.get("model", 1) if mdl is not None else 1
    if s % max(g, 1) or g <= 1:
        g = 1
    sg = s // g
    cap = max(int(math.ceil(sg * k * cfg.capacity_factor / e)), 4)
    ep = rules.maybe(e, "model")
    grp = P(dp, "model" if g > 1 else None, None, None, None)

    xg = x.reshape(bsz, g, sg, d)
    xg = shard_act(xg, P(dp, "model" if g > 1 else None, None, None), rules)
    scores = (xg @ p[f"{prefix}/router"]).astype(jnp.float32)   # [B,G,Sg,E]
    gates = jax.nn.softmax(scores, axis=-1)
    topw, topi = jax.lax.top_k(gates, k)                        # [B,G,Sg,K]
    topw = (topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    flat_i = topi.reshape(bsz, g, sg * k)                       # [B,G,N]
    onehot = jax.nn.one_hot(flat_i, e, dtype=jnp.int32)
    ranks_all = jnp.cumsum(onehot, axis=2) - onehot             # local cumsum
    rank = jnp.take_along_axis(ranks_all, flat_i[..., None], axis=-1)[..., 0]
    keep = rank < cap
    rank_c = jnp.minimum(rank, cap - 1)

    updates = jnp.repeat(xg, k, axis=2) * keep[..., None].astype(x.dtype)
    # scatter with EXPLICIT batch dims (vmap over B and G): GSPMD then
    # partitions the scatter over dp x model instead of replicating — a
    # 4-index-array scatter hides the batch structure from the partitioner
    scat = jax.vmap(jax.vmap(lambda b, i, r, u: b.at[i, r].add(u)))
    buf = jnp.zeros((bsz, g, e, cap, d), x.dtype)
    buf = shard_act(buf, grp, rules)
    buf = scat(buf, flat_i, rank_c, updates)                    # fully local
    buf = shard_act(buf, grp, rules)
    # THE all-to-all: G("model") -> E("model")
    buf = shard_act(buf, P(dp, None, ep, None, None), rules)

    h = jnp.einsum("bgecd,edf->bgecf", buf, p[f"{prefix}/w_in"])
    if cfg.mlp_variant == "swiglu":
        gg = jnp.einsum("bgecd,edf->bgecf", buf, p[f"{prefix}/w_gate"])
        h = jax.nn.silu(gg) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("bgecf,efd->bgecd", h, p[f"{prefix}/w_out"])
    # all-to-all back: E("model") -> G("model")
    y = shard_act(y, grp, rules)

    gath = jax.vmap(jax.vmap(lambda yy, i, r: yy[i, r]))
    out = gath(y, flat_i, rank_c)                               # [B,G,N,D]
    out = out * (topw.reshape(bsz, g, sg * k) * keep.astype(x.dtype))[..., None]
    return out.reshape(bsz, g, sg, k, d).sum(axis=3).reshape(bsz, s, d)


class MoEModel(DenseModel):
    """Dense attention + MoE FFN (+ optional parallel dense-residual MLP)."""

    def _build_block(self):
        cfg, rules = self.cfg, self.rules

        def build(key):
            b = ParamBuilder(key, cfg.pdtype)
            init_norm(b, "ln1", cfg.d_model)
            init_attn(b, cfg, rules)
            init_norm(b, "ln2", cfg.d_model)
            init_moe_ffn(b, cfg, rules)
            if cfg.dense_residual:
                init_mlp(b, cfg, rules, prefix="dense_mlp")
            return b.params, b.specs

        return build

    def _apply_block(self, p, x, *, positions, cache=None, layer=None,
                     q_chunk=None, window=None):
        cfg = self.cfg
        h, new_cache = apply_attn(p, cfg, rmsnorm(x, p["ln1"], cfg.eps),
                                  positions=positions, cache=cache,
                                  layer=layer, q_chunk=q_chunk, window=window)
        x = shard_act(x + h, self.act_spec, self.rules)
        hn = rmsnorm(x, p["ln2"], cfg.eps)
        y = moe_ffn(p, cfg, hn, self.rules)
        if cfg.dense_residual:
            y = y + mlp(p, cfg, hn, prefix="dense_mlp")
        return shard_act(x + y, self.act_spec, self.rules), new_cache
