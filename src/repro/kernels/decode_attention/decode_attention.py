"""Flash-decode GQA attention kernel (one query token vs a long KV cache).

This is the decode_32k / long_500k hot spot: q (B, KV, G, D) against a
head-major cache k/v (B, KV, S, D) with a per-batch valid length.  TPU
mapping:

* grid (B, KV, S/block_s) — the innermost axis iterates sequentially on a
  TPU core, so the online-softmax running state (m, l, acc) lives in VMEM
  scratch and carries across KV-cache blocks;
* BlockSpecs stream one (block_s, D) tile of K and V per grid step
  HBM->VMEM (the kernel is memory-bound: arithmetic intensity ~ G, so the
  goal is pure streaming at HBM bandwidth with no (S,) materialization).
  The cache is head-major so each tile's last two dims are (block_s, D):
  Mosaic requires a block's last two dims to be multiples of (8, 128) or
  the full array dims, which a (1, D) head slice of a (KV, D) tail is not;
* ``lengths`` is a scalar-prefetch operand: the whole (B,) vector sits in
  SMEM and the kernel reads its own row's entry.

The q tile (G, D) stays resident; scores are (G, block_s) f32 in registers/
VMEM; the final normalization writes (G, D) once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, scale: float):
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (block_s, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (block_s, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    length = len_ref[b_idx]
    offs = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                      s.shape, 1)
    s = jnp.where(offs < length, s, NEG_INF)

    m_prev = m_ref[...]                                   # (G, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                # (G, bs)
    alpha = jnp.exp(m_prev - m_new)                       # (G, 1)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, block_s: int = 512,
                            interpret: bool = True):
    """q: (B, KV, G, D); k, v: (B, KV, S, D); lengths: (B,) int32.

    Returns (B, KV, G, D)."""
    b, kvh, g, d = q.shape
    s = k.shape[2]
    block_s = min(block_s, s)
    assert s % block_s == 0, (s, block_s)
    n_s = s // block_s
    scale = d ** -0.5
    kernel = functools.partial(_kernel, block_s=block_s, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda bi, hi, si, lens: (bi, hi, si, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda bi, hi, si, lens: (bi, hi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bi, hi, si, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths, q, k, v)
