"""Operations and bytes the work needs, from shapes alone.

These are the numerators of every share of a peak or a roofline. They
count what the algorithm needs, not what an implementation happens to do:
padding, recomputation and replicated work are waste, not work.

LM counts take a configuration file's keys (``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``vocab_size``). Multiply-adds
count two operations.
"""
from __future__ import annotations

__all__ = ["layer_params", "prefill_flops", "decode_flops", "kv_bytes",
           "MC_OPS_PER_PATH_STEP", "mc_ops"]


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])


def layer_params(cfg: dict) -> int:
    """Matrix weights of one decoder layer (q, k, v, o and a gated MLP)."""
    d, f, _, h, kv, hd, _ = _dims(cfg)
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f


def _attn_flops(cfg: dict, keys: int) -> float:
    """Score and value products for queries that attend ``keys`` keys in
    all: 2 products x 2 operations x heads x head_dim, in every layer."""
    _, _, n_l, h, _, hd, _ = _dims(cfg)
    return 4.0 * n_l * h * hd * keys


def prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """One prefill of ``batch`` prompts of ``prompt`` tokens: every layer
    over every token, causal attention (token i attends i + 1 keys), and
    the output head for the last position only, which is all a prefill
    that returns the next token needs."""
    d, _, n_l, _, _, _, v = _dims(cfg)
    tokens = batch * prompt
    causal = batch * prompt * (prompt + 1) // 2
    return (2.0 * layer_params(cfg) * n_l * tokens
            + _attn_flops(cfg, causal) + 2.0 * batch * d * v)


def decode_flops(cfg: dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` rows whose new token sits at position
    ``pos`` (so it attends pos + 1 keys), output head included."""
    d, _, n_l, _, _, _, v = _dims(cfg)
    return (2.0 * (layer_params(cfg) * n_l + d * v) * batch
            + _attn_flops(cfg, batch * (pos + 1)))


def kv_bytes(cfg: dict, batch: int, pos: int, itemsize: int = 2,
             shards: int = 1) -> float:
    """Keys and values one decode step must read (positions 0..pos-1) and
    write (position pos), on one of ``shards`` devices that split the kv
    heads."""
    _, _, n_l, _, kv, hd, _ = _dims(cfg)
    return 2.0 * n_l * batch * (pos + 1) * (kv // shards) * hd * itemsize


#: 32-bit elementwise operations one path-step needs, by model kind. A
#: transcendental (log, sqrt, sin, cos, exp) counts as one, which is less
#: than it costs, so shares built on these read low rather than high.
#: - Threefry-2x32, 20 rounds: 2 key adds, 20 x (add, shl, shr, or, xor),
#:   5 key injections of 3 adds: 117.
#: - two uniforms (shift, int->float, add, scale): 8.
#: - Box-Muller (log, scale, sqrt, angle, cos, sin, 2 products): 8.
#: - running sum, min and max: 3.
#: - the step: log-Euler Black-Scholes 4 (product, add, exp, product);
#:   full-truncation Heston 21 (correlated shock 3, clamp 1, sqrt 1,
#:   drift 3, diffusion 2, add, exp, product; variance update 8).
MC_OPS_PER_PATH_STEP = {"black-scholes": 117 + 8 + 8 + 3 + 4,
                        "heston": 117 + 8 + 8 + 3 + 21}


def mc_ops(kind: str, n_paths: int, n_steps: int) -> float:
    """Operations to simulate ``n_paths`` paths of ``n_steps`` steps."""
    return float(MC_OPS_PER_PATH_STEP[kind]) * n_paths * n_steps
