"""Pure-jnp oracle for the flash-decode kernel."""
from __future__ import annotations

import jax.numpy as jnp
import jax


def decode_attention_ref(q, k, v, lengths):
    """q: (B, KV, G, D); k, v: (B, KV, S, D); lengths: (B,)."""
    b, kvh, g, d = q.shape
    s = k.shape[2]
    qf = q.astype(jnp.float32) * (d ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bksd->bkgs", qf, kf)
    valid = jnp.arange(s)[None, :] < lengths[:, None]       # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vf)
    return out.astype(q.dtype)
