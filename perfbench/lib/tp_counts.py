"""The benchmark's own count of the tensor-parallel exchange, from a
configuration file's keys alone.

A tensor-parallel step over ``tp`` devices shards the attention heads, the
MLP's hidden units and the output head's vocabulary, and gathers each back
to full width where a contraction needs it: a layer's attention output
([rows, positions, heads x head_dim]) and its MLP hidden activations
([rows, positions, intermediate_size]), and the logits of the positions
the step returns ([rows, positions, vocab_size]), all in the
configuration's dtype. One device holds 1/tp of each gathered tensor and
receives the rest. These are what the program's ``serve.prefill`` and
``serve.decode`` spans should carry as ``collectives`` and
``exchange_bytes``.
"""
from __future__ import annotations

__all__ = ["ITEMSIZE", "exchange", "prefill_exchange", "decode_exchange"]

#: Bytes an element of each ``torch_dtype`` takes.
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def exchange(cfg: dict, rows: int, positions: int, logits: int,
             tp: int) -> tuple:
    """(all-gathers, bytes one device receives) for one call over
    ``positions`` positions of ``rows`` rows that returns ``logits``
    positions' logits; (0, 0) on one device."""
    if tp < 2:
        return 0, 0
    layers = cfg["num_hidden_layers"]
    width = (cfg["num_attention_heads"] * cfg["head_dim"]
             + cfg["intermediate_size"])
    gathered = (layers * rows * positions * width
                + rows * logits * cfg["vocab_size"])
    item = ITEMSIZE[cfg["torch_dtype"]]
    return 2 * layers + 1, gathered * item * (tp - 1) // tp


def prefill_exchange(cfg: dict, rows: int, prompt: int) -> tuple:
    """One prefill: every prompt position, the last one's logits."""
    return exchange(cfg, rows, prompt, 1, int(cfg["tp"]))


def decode_exchange(cfg: dict, rows: int) -> tuple:
    """One decode step: one position and its logits."""
    return exchange(cfg, rows, 1, 1, int(cfg["tp"]))
