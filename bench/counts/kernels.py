"""FLOPs and HBM bytes that one call of each kernel needs.

Counted from the call's own batch, heads and valid lengths, not from what
the kernel happens to stream: ``decode_attention`` needs K and V over the
``length`` positions that hold a token, not over the whole cache, and
``swa_prefill`` needs the causal pairs that lie inside the window.  So a
kernel that stops streaming padding moves closer to its roofline instead of
past it.  Operands are bfloat16 (2 bytes); the softmax's exponentials are
not counted as FLOPs.
"""
from __future__ import annotations

BYTES = 2


def causal_pairs(s: int, window: int) -> int:
    """(query, key) pairs with 0 <= q - k < window among s positions."""
    w = min(window, s) if window else s
    # rows 0..w-1 see 1..w keys; the remaining s - w rows see w keys each
    return w * (w + 1) // 2 + (s - w) * w


def decode_attention(b: int, heads: int, kv_heads: int, head_dim: int,
                     length: int) -> tuple[float, float]:
    """One call over b rows, each with ``length`` valid cache positions."""
    flops = 4.0 * b * heads * head_dim * length          # QK^T and PV
    kv = 2.0 * b * kv_heads * length * head_dim * BYTES  # K and V
    qo = 2.0 * b * heads * head_dim * BYTES              # q in, out
    return flops, kv + qo


def swa_prefill(b: int, heads: int, kv_heads: int, head_dim: int, s: int,
                window: int) -> tuple[float, float]:
    """One call over b prompts of s tokens (window 0 = full causal)."""
    flops = 4.0 * b * heads * head_dim * causal_pairs(s, window)
    qo = 2.0 * b * heads * s * head_dim * BYTES
    kv = 2.0 * b * kv_heads * s * head_dim * BYTES
    return flops, qo + kv
