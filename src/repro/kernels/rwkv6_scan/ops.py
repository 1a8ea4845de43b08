"""Jitted public wrapper for the WKV6 kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.platform import interpret_mode
from repro.kernels.rwkv6_scan.rwkv6_scan import rwkv6_scan_pallas


@partial(jax.jit, static_argnames=("block_t",))
def rwkv6_scan(r, k, v, w, u, s0, block_t: int = 256):
    """WKV6 recurrence.  r,k,v,w: (B,T,H,D); u: (H,D); s0: (B,H,D,D).
    Returns (y, s_final)."""
    return rwkv6_scan_pallas(r, k, v, w, u, s0, block_t=block_t,
                             interpret=interpret_mode())
