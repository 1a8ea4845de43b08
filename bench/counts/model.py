"""Model FLOPs of the tokens a dense decoder serves (for ``step.mfu``): the
counts of ``bench/configs/decoder_adapter.py``.

A matmul of an (n, k) by (k, m) operand is 2·n·k·m FLOPs.  Prefill of a
p-token prompt runs every layer over the p tokens, attention over the
causal pairs inside the window, and the head once (only the last position's
logits are needed).  Each decoded token runs every layer and the head once,
with attention over the positions it can see.
"""
from __future__ import annotations

from bench.counts.kernels import causal_pairs


def layer_matmul_params(cfg: dict) -> int:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff


def prefill_flops(cfg: dict, p: int) -> float:
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    w = int(cfg.get("sliding_window") or 0)
    return (2.0 * L * layer_matmul_params(cfg) * p
            + 4.0 * L * h * hd * causal_pairs(p, w)
            + 2.0 * d * cfg["vocab_size"])


def decode_flops(cfg: dict, position: int) -> float:
    """One token at 0-based ``position`` (it attends to position + 1 keys,
    or the window)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    w = int(cfg.get("sliding_window") or 0)
    ctx = min(position + 1, w) if w else position + 1
    return (2.0 * L * layer_matmul_params(cfg) + 4.0 * L * h * hd * ctx
            + 2.0 * d * cfg["vocab_size"])
