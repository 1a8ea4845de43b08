"""Jitted public wrapper: GQA sliding-window prefill attention."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.platform import interpret_mode
from repro.kernels.swa_prefill.swa_prefill import swa_prefill_pallas


@partial(jax.jit, static_argnames=("window", "block"))
def swa_prefill_attention(q, k, v, window: int, block: int = 256):
    """Causal SWA prefill.  q: (B, S, H, D); k, v: (B, S, KV, D) with
    H % KV == 0.  The kernel takes head-major operands; query head h
    attends kv head h // (H // KV).  Returns (B, S, H, D)."""
    def head_major(x):
        return x.transpose(0, 2, 1, 3)
    out = swa_prefill_pallas(head_major(q), head_major(k), head_major(v),
                             window=window, block_q=block, block_k=block,
                             interpret=interpret_mode())
    return head_major(out)
