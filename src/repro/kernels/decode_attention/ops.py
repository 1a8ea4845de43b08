"""Jitted public wrapper for the flash-decode kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention.decode_attention import \
    decode_attention_pallas
from repro.kernels.platform import interpret_mode


@partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q, k, v, lengths, block_s: int = 512):
    """Flash-decode GQA attention.  q: (B, KV, G, D); k/v: (B, KV, S, D)
    (head-major cache); lengths: (B,) int32 valid cache lengths.
    Returns (B, KV, G, D)."""
    return decode_attention_pallas(q, k, v, lengths, block_s=block_s,
                                   interpret=interpret_mode())
