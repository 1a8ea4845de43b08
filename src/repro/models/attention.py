"""Attention mixers: GQA/MQA, sliding-window, and DeepSeek-style MLA.

Pure functional: ``init_*`` builds a param dict, ``*_fwd`` runs train/prefill,
``*_decode`` runs a single-token step against a KV cache.

Prefill/train uses a double-blocked (flash-style) online-softmax attention in
pure jnp (``blocked_attention``) so the 32k-token shapes never materialize a
full (S, S) score matrix.  Decode computes scores against the whole cache
directly — that is the hot spot the Pallas ``decode_attention`` kernel
implements for TPU (see src/repro/kernels/decode_attention).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (Array, apply_mrope, apply_rope, dense_init,
                                 linear, rms_norm, softcap)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Standard / GQA / SWA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype, *, num_heads=None,
                   num_kv_heads=None, head_dim=None):
    h = num_heads or cfg.num_heads
    kv = num_kv_heads or cfg.num_kv_heads
    d = head_dim or cfg.head_dim
    dm = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (dm, h * d), dtype),
        "wk": dense_init(ks[1], (dm, kv * d), dtype),
        "wv": dense_init(ks[2], (dm, kv * d), dtype),
        "wo": dense_init(ks[3], (h * d, dm), dtype, fan_in=h * d),
    }


def _rope_qk(q, k, positions, cfg: ModelConfig, k_positions=None):
    if cfg.rope_kind == "standard":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if k_positions is None else k_positions,
                       cfg.rope_theta)
    elif cfg.rope_kind == "mrope":
        # positions here is (3, B, S)
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k


def blocked_attention(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
                      *, causal: bool, window: int, scale: float,
                      cap: float = 0.0, block_q: int = 512,
                      block_k: int = 1024) -> Array:
    """Flash-style online-softmax attention in pure jnp.

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D); q_pos/k_pos: (B, Sq)/(B, Sk).
    Returns (B, Sq, H, D).  Never materializes (Sq, Sk).
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kvh
    orig_sq = sq
    # pad sq/sk to block multiples
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
        sq += pad_q
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_k)), constant_values=2**30)
        sk += pad_k
    nq, nk = sq // block_q, sk // block_k

    qb = q.reshape(b, nq, block_q, kvh, g, d).transpose(1, 0, 3, 4, 2, 5)
    # qb: (nq, B, KV, G, bq, D)
    qpb = q_pos.reshape(b, nq, block_q).transpose(1, 0, 2)  # (nq, B, bq)
    kb = k.reshape(b, nk, block_k, kvh, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, block_k, kvh, dv).transpose(1, 0, 3, 2, 4)
    kpb = k_pos.reshape(b, nk, block_k).transpose(1, 0, 2)  # (nk, B, bk)

    def q_block(args):
        qi, qp = args  # (B,KV,G,bq,D), (B,bq)
        qi = qi.astype(jnp.float32) * scale

        def kv_step(carry, kv_args):
            m, l, acc = carry
            ki, vi, kp = kv_args  # (B,KV,bk,D) x2, (B,bk)
            s = jnp.einsum("bkgqd,bktd->bkgqt", qi, ki.astype(jnp.float32))
            if cap > 0:
                s = softcap(s, cap)
            mask = jnp.ones(s.shape[-2:], bool)[None, None, None]
            rel = qp[:, None, None, :, None] - kp[:, None, None, None, :]
            if causal:
                mask = mask & (rel >= 0)
            if window > 0:
                mask = mask & (rel < window)
            mask = mask & (kp < 2**30)[:, None, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, block_q), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, block_q, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, kpb))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(q_block, (qb, qpb))           # (nq,B,KV,G,bq,Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)
    return out[:, :orig_sq].astype(v.dtype)


def _bp_spec(mesh, batch: int):
    """Widest mesh-axes tuple that divides the batch (for batch-parallel
    attention: shard the batch over the model axis too — archs whose head
    counts don't divide the model axis otherwise run attention replicated
    n_model times; EXPERIMENTS.md §Perf smollm iteration)."""
    from jax.sharding import PartitionSpec as P
    names = list(mesh.axis_names)
    for axes in (tuple(names), tuple(a for a in names if a != "pod")):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if axes and batch % n == 0:
            return axes
    return None


def _bp_constrain(x, mesh, axes):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def attention_fwd(params, x: Array, positions, cfg: ModelConfig, *,
                  window: int = 0, causal: bool = True,
                  kv_x: Optional[Array] = None, kv_positions=None,
                  num_heads=None, num_kv_heads=None, head_dim=None,
                  mesh=None) -> Array:
    """Train/prefill attention.  kv_x != None => cross-attention."""
    h = num_heads or cfg.num_heads
    kvh = num_kv_heads or cfg.num_kv_heads
    d = head_dim or cfg.head_dim
    b, s, _ = x.shape
    src = kv_x if kv_x is not None else x
    sk = src.shape[1]
    q = linear(x, params["wq"]).reshape(b, s, h, d)
    k = linear(src, params["wk"]).reshape(b, sk, kvh, d)
    v = linear(src, params["wv"]).reshape(b, sk, kvh, d)
    if kv_x is None and cfg.rope_kind in ("standard", "mrope"):
        q, k = _rope_qk(q, k, positions, cfg)
    qp = positions if cfg.rope_kind != "mrope" else positions[0]
    if kv_x is not None:
        kp = kv_positions
        if kp is None:
            kp = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    else:
        kp = qp
    bp_axes = (_bp_spec(mesh, b)
               if (mesh is not None and cfg.attn_batch_parallel) else None)
    if bp_axes:
        q = _bp_constrain(q, mesh, bp_axes)
        k = _bp_constrain(k, mesh, bp_axes)
        v = _bp_constrain(v, mesh, bp_axes)
    out = blocked_attention(q, k, v, qp, kp, causal=causal and kv_x is None,
                            window=window, scale=d ** -0.5,
                            cap=cfg.logit_softcap)
    if bp_axes:
        out = _bp_constrain(out, mesh, bp_axes)
    return linear(out.reshape(b, s, h * d), params["wo"])


def attention_decode(params, x: Array, cache: dict, cache_index: Array,
                     positions, cfg: ModelConfig, *, window: int = 0,
                     num_heads=None, num_kv_heads=None, head_dim=None):
    """Single-token decode.  x: (B, 1, d_model).

    cache: {"k": (B, KV, S, D), "v": ...} — head-major, so the Pallas
    decode kernel streams (block_s, D) tiles; S is the window size for SWA
    (ring buffer) or max_seq for full attention.  Keys are cached post-RoPE.
    Returns (y, new_cache).
    """
    h = num_heads or cfg.num_heads
    kvh = num_kv_heads or cfg.num_kv_heads
    d = head_dim or cfg.head_dim
    b = x.shape[0]
    q = linear(x, params["wq"]).reshape(b, 1, h, d)
    k = linear(x, params["wk"]).reshape(b, 1, kvh, d)
    v = linear(x, params["wv"]).reshape(b, 1, kvh, d)
    if cfg.rope_kind in ("standard", "mrope"):
        q, k = _rope_qk(q, k, positions, cfg)

    s_cache = cache["k"].shape[2]
    slot = cache_index % s_cache if window > 0 else cache_index
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
        (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
        (0, 0, slot, 0))

    # validity mask over cache slots
    j = jnp.arange(s_cache)
    if window > 0:
        # ring buffer: slot j holds position index - ((slot - j) mod S)
        age = (slot - j) % s_cache
        valid = age <= cache_index
    else:
        valid = j <= cache_index

    g = h // kvh
    if (cfg.use_pallas_decode and window == 0 and cfg.logit_softcap == 0
            and d % 8 == 0 and (s_cache <= 512 or s_cache % 512 == 0)):
        # Pallas flash-decode kernel path (kernels/decode_attention):
        # contiguous cache [0..index] -> lengths mask
        from repro.kernels.decode_attention.ops import decode_attention
        lengths = jnp.broadcast_to(cache_index + 1, (b,)).astype(jnp.int32)
        qk = q.reshape(b, kvh, g, d)
        out = decode_attention(qk, ck, cv, lengths,
                               block_s=min(512, s_cache))
        out = out.reshape(b, 1, h * d).astype(x.dtype)
        y = linear(out, params["wo"])
        return y, {"k": ck, "v": cv}
    qf = (q.reshape(b, kvh, g, d) * (d ** -0.5)).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bksd->bkgs", qf, ck.astype(jnp.float32))
    if cfg.logit_softcap > 0:
        scores = softcap(scores, cfg.logit_softcap)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, cv.astype(jnp.float32))
    out = out.reshape(b, 1, h * d).astype(x.dtype)
    y = linear(out, params["wo"])
    return y, {"k": ck, "v": cv}


def init_attention_cache(cfg: ModelConfig, batch: int, seq: int, dtype, *,
                         window: int = 0, num_kv_heads=None, head_dim=None):
    kvh = num_kv_heads or cfg.num_kv_heads
    d = head_dim or cfg.head_dim
    s = min(seq, window) if window > 0 else seq
    shape = (batch, kvh, s, d)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# MLA — DeepSeek multi-head latent attention
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    dm, h = cfg.d_model, cfg.num_heads
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_head_dim
    ks = jax.random.split(key, 8)
    return {
        "w_dq": dense_init(ks[0], (dm, cfg.q_lora_rank), dtype),
        "q_norm": jnp.zeros((cfg.q_lora_rank,), dtype),
        "w_uq": dense_init(ks[1], (cfg.q_lora_rank, h * (nope + rope_d)), dtype),
        "w_dkv": dense_init(ks[2], (dm, cfg.kv_lora_rank + rope_d), dtype),
        "kv_norm": jnp.zeros((cfg.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[3], (cfg.kv_lora_rank, h * nope), dtype),
        "w_uv": dense_init(ks[4], (cfg.kv_lora_rank, h * vd), dtype),
        "wo": dense_init(ks[5], (h * vd, dm), dtype, fan_in=h * vd),
    }


def _mla_qkv(params, x, positions, cfg: ModelConfig):
    """Shared projection logic. Returns q_nope, q_rope, c_kv, k_rope."""
    b, s, _ = x.shape
    h, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = rms_norm(linear(x, params["w_dq"]), params["q_norm"], cfg.norm_eps)
    q = linear(cq, params["w_uq"]).reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = linear(x, params["w_dkv"])
    c_kv = rms_norm(ckv[..., :cfg.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = ckv[..., cfg.kv_lora_rank:][:, :, None, :]      # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_fwd(params, x: Array, positions, cfg: ModelConfig) -> Array:
    """Train/prefill MLA: materialize per-head K/V from the latent."""
    b, s, _ = x.shape
    h, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_head_dim
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, positions, cfg)
    k_nope = linear(c_kv, params["w_uk"]).reshape(b, s, h, nope)
    v = linear(c_kv, params["w_uv"]).reshape(b, s, h, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope_d))],
                        axis=-1)
    pos = positions
    out = blocked_attention(q, k, v, pos, pos, causal=True, window=0,
                            scale=(nope + rope_d) ** -0.5)
    return linear(out.reshape(b, s, h * vd), params["wo"])


def mla_decode(params, x: Array, cache: dict, cache_index: Array, positions,
               cfg: ModelConfig):
    """Absorbed-matrix MLA decode: attend in the compressed latent space.

    cache: {"c_kv": (B, S, kv_lora), "k_rope": (B, S, rope_d)} — ~10x smaller
    than a materialized GQA cache; the per-head K/V never exist at decode.
    """
    b = x.shape[0]
    h, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    vd, r = cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, positions, cfg)
    c_kv_cache = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, cache_index, 0))
    k_rope_cache = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype),
        (0, cache_index, 0))
    # absorb W_uk into q: q_eff (B,H,r)
    w_uk = params["w_uk"].reshape(r, h, nope)
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = (nope + rope_d) ** -0.5
    s_lat = jnp.einsum("bhr,bsr->bhs", q_eff,
                       c_kv_cache.astype(jnp.float32)) * scale
    s_rope = jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                        k_rope_cache.astype(jnp.float32)) * scale
    scores = s_lat + s_rope
    valid = jnp.arange(scores.shape[-1]) <= cache_index
    scores = jnp.where(valid[None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, c_kv_cache.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(r, h, vd)
    out = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
    out = out.reshape(b, 1, h * vd).astype(x.dtype)
    y = linear(out, params["wo"])
    return y, {"c_kv": c_kv_cache, "k_rope": k_rope_cache}


def init_mla_cache(cfg: ModelConfig, batch: int, seq: int, dtype):
    return {"c_kv": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, seq, cfg.qk_rope_dim), dtype)}
