"""Sliding-window prefill flash attention with window block-skipping.

The pure-jnp blocked attention computes every (q_block, kv_block) pair and
masks — O(S^2) work even when the window W << S.  This kernel's grid is
(B, H, S/block_q, W/block_k + 1): for each q block only the kv blocks that
can intersect its window are visited, so prefill work is O(S * W) — an
8x reduction for h2o-danube's prefill_32k (W=4096, S=32768).

TPU mapping:
* operands are head-major (B, heads, S, D), so every tile's last two dims
  are (block, D) — a legal Mosaic block (multiples of (8, 128) or the full
  array dims); query head h reads kv head h // G (GQA without repeating
  K/V in HBM);
* the kv BlockSpec index_map computes the ABSOLUTE kv block
  `qi + wi - n_w + 1` (clamped at 0) — the harness streams exactly the
  window-diagonal band HBM->VMEM;
* the q tile and the online-softmax state (m, l, acc scratch) persist
  across the innermost (wi) axis, finalized on the last window block;
* clamped duplicate blocks are killed in-kernel by the `expected >= 0`
  test plus the causal/window position mask (f32 accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, n_w: int, window: int,
            scale: float):
    qi = pl.program_id(2)
    wi = pl.program_id(3)

    @pl.when(wi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    expected = qi + wi - (n_w - 1)          # absolute kv block (pre-clamp)

    @pl.when(expected >= 0)
    def _work():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = expected * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                              s.shape, 1)
        rel = q_pos - k_pos
        mask = (rel >= 0) & (rel < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(wi == n_w - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def swa_prefill_pallas(q, k, v, *, window: int, block_q: int = 256,
                       block_k: int = 256, interpret: bool = True):
    """Causal sliding-window GQA attention on head-major operands.

    q: (B, H, S, D); k, v: (B, KV, S, D) with H % KV == 0.
    Returns (B, H, S, D)."""
    b, h, s, d = q.shape
    g = h // k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert block_q == block_k, "kernel requires equal q/kv block sizes"
    assert s % block_q == 0
    # kv blocks that can intersect a q block's window (incl. the diagonal)
    n_w = (window + block_q - 2) // block_k + 1
    n_w = min(n_w, s // block_k)
    grid = (b, h, s // block_q, n_w)
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               n_w=n_w, window=window, scale=d ** -0.5)

    def q_index(bi, hi, qi, wi):
        return (bi, hi, qi, 0)

    def kv_index(bi, hi, qi, wi):
        return (bi, hi // g, jnp.maximum(qi + wi - (n_w - 1), 0), 0)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="swa_prefill",
    )(q, k, v)
