"""Stack assembly: blocks, scan-over-layers, prefill/decode plumbing.

A model is a sequence of homogeneous *groups* of blocks (e.g. DeepSeek-V3 is
3x "mla+mlp" then 58x "mla+moe"); each group is init'd with stacked params
(leading L dim) and executed with ``lax.scan`` so the HLO stays compact for
61-layer models.  Zamba2's single SHARED attention block is closed over by
the scan body and applied every ``shared_attn_every`` layers via
``lax.cond``, with its per-application KV cache carried through the scan.
"""
from __future__ import annotations

from itertools import groupby

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import rwkv6 as rk
from repro.models.common import linear, rms_norm
from repro.models.mlp import init_mlp, mlp_fwd
from repro.models.moe import init_moe, moe_fwd, moe_fwd_ep

EP_TOKEN_THRESHOLD = 4096  # below this, the single-shard MoE path is used


def layer_groups(cfg: ModelConfig) -> list[tuple[str, int]]:
    return [(kind, len(list(g))) for kind, g in groupby(cfg.blocks)]


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def _ffn_dim(cfg: ModelConfig, kind: str) -> int:
    return cfg.d_ff


def init_block(key, cfg: ModelConfig, kind: str, dtype):
    mixer, ffn = kind.split("+")
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"norm1": jnp.zeros((d,), dtype)}
    if mixer in ("attn", "swa"):
        p["attn"] = attn.init_attention(ks[0], cfg, dtype)
    elif mixer == "mla":
        p["mla"] = attn.init_mla(ks[0], cfg, dtype)
    elif mixer == "mamba2":
        p["mamba"] = m2.init_mamba2(ks[0], cfg, dtype)
    elif mixer == "rwkv6":
        p["tmix"] = rk.init_rwkv6_tmix(ks[0], cfg, dtype)
    if ffn != "none":
        p["norm2"] = jnp.zeros((d,), dtype)
    if ffn == "mlp":
        p["mlp"] = init_mlp(ks[1], d, _ffn_dim(cfg, kind), cfg.mlp_kind, dtype)
    elif ffn == "moe":
        p["moe"] = init_moe(ks[1], cfg, dtype)
    elif ffn == "rwkv_cm":
        p["cmix"] = rk.init_rwkv6_cmix(ks[1], cfg, dtype)
    if cfg.is_encoder_decoder:
        p["norm_cross"] = jnp.zeros((d,), dtype)
        p["cross"] = attn.init_attention(ks[2], cfg, dtype)
    return p


def init_shared_attn(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    return {
        "norm1": jnp.zeros((d,), dtype),
        "attn": attn.init_attention(ks[0], cfg, dtype),
        "norm2": jnp.zeros((d,), dtype),
        "mlp": init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_kind, dtype),
    }


# ---------------------------------------------------------------------------
# Block forward (train — no caches) / prefill (returns caches) / decode
# ---------------------------------------------------------------------------

def _mixer_fwd(p, x, ctx, mixer, cfg, state=None):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer in ("attn", "swa"):
        w = cfg.window_size if mixer == "swa" else 0
        return attn.attention_fwd(p["attn"], h, ctx["positions"], cfg,
                                  window=w, causal=ctx.get("causal", True),
                                  mesh=ctx.get("mesh")), None
    if mixer == "mla":
        return attn.mla_fwd(p["mla"], h, ctx["positions"], cfg), None
    if mixer == "mamba2":
        y, st = m2.mamba2_fwd(p["mamba"], h, cfg, state)
        return y, st
    if mixer == "rwkv6":
        y, st = rk.rwkv6_tmix_fwd(p["tmix"], h, cfg, state)
        return y, st
    raise ValueError(mixer)


def _ffn_fwd(p, x, ctx, ffn, cfg, mesh, state=None):
    if ffn == "none":
        return jnp.zeros_like(x), jnp.float32(0.0), None
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if ffn == "mlp":
        return mlp_fwd(p["mlp"], h, cfg.mlp_kind), jnp.float32(0.0), None
    if ffn == "moe":
        if mesh is not None:
            y, aux = moe_fwd_ep(p["moe"], h, cfg, mesh,
                                ctx["data_axes"], ctx["model_axis"])
        else:
            y, aux = moe_fwd(p["moe"], h, cfg)
        return y, aux, None
    if ffn == "rwkv_cm":
        y, st = rk.rwkv6_cmix_fwd(p["cmix"], h, cfg, state)
        return y, jnp.float32(0.0), st
    raise ValueError(ffn)


def _cross_fwd(p, x, ctx, cfg):
    h = rms_norm(x, p["norm_cross"], cfg.norm_eps)
    return attn.attention_fwd(p["cross"], h, ctx["positions"], cfg,
                              causal=False, kv_x=ctx["enc_out"])


def block_fwd(p, x, ctx, kind, cfg: ModelConfig, mesh):
    mixer, ffn = kind.split("+")
    y, _ = _mixer_fwd(p, x, ctx, mixer, cfg)
    x = x + y
    if cfg.is_encoder_decoder and ctx.get("enc_out") is not None:
        x = x + _cross_fwd(p, x, ctx, cfg)
    y, aux, _ = _ffn_fwd(p, x, ctx, ffn, cfg, mesh)
    x = x + y
    return x, aux


# -- prefill: same math, but also build the decode cache ---------------------

def _write_kv_cache(k, v, positions, cache_size, window):
    """Arrange full-sequence K/V (B,S,KV,D) into a head-major decode cache
    (B,KV,cache,D).

    Full attention: cache[:, :, :S] = kv (cache_size >= S).
    SWA: ring buffer of size window — slot p%W holds position p (last W)."""
    b, s, kvh, d = k.shape
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if window > 0:
        w = min(window, cache_size)
        take = min(s, w)
        ks_, vs_ = k[:, :, -take:], v[:, :, -take:]
        pos = positions[0, -take:] % w
        ck = jnp.zeros((b, kvh, w, d), k.dtype).at[:, :, pos].set(ks_)
        cv = jnp.zeros((b, kvh, w, d), v.dtype).at[:, :, pos].set(vs_)
        return {"k": ck, "v": cv}
    pad = cache_size - s
    ck = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    cv = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return {"k": ck, "v": cv}


def _attn_prefill(p, h, ctx, cfg, window, cache_size):
    """Attention fwd that also returns the populated decode cache."""
    b, s, _ = h.shape
    hh = cfg.num_heads
    kvh, d = cfg.num_kv_heads, cfg.head_dim
    q = linear(h, p["wq"]).reshape(b, s, hh, d)
    k = linear(h, p["wk"]).reshape(b, s, kvh, d)
    v = linear(h, p["wv"]).reshape(b, s, kvh, d)
    positions = ctx["positions"]
    if cfg.rope_kind in ("standard", "mrope"):
        q, k = attn._rope_qk(q, k, positions, cfg)
    qp = positions if cfg.rope_kind != "mrope" else positions[0]
    mesh = ctx.get("mesh")
    bp_axes = (attn._bp_spec(mesh, b)
               if (mesh is not None and cfg.attn_batch_parallel) else None)
    if bp_axes:
        q = attn._bp_constrain(q, mesh, bp_axes)
        k = attn._bp_constrain(k, mesh, bp_axes)
        v = attn._bp_constrain(v, mesh, bp_axes)
    # Pallas swa_prefill kernel route (serving prefill): the kernel is
    # causal-SWA, so full attention is window >= S; usable when nothing
    # needs the pure-jnp path's extras (softcap, batch-parallel shards,
    # non-divisible block shapes)
    if (cfg.use_pallas_prefill and cfg.logit_softcap == 0
            and bp_axes is None and (s <= 256 or s % 256 == 0)):
        from repro.kernels.swa_prefill.ops import swa_prefill_attention
        out = swa_prefill_attention(q, k, v,
                                    window=window if window > 0 else s,
                                    block=min(256, s))
    else:
        out = attn.blocked_attention(q, k, v, qp, qp, causal=True,
                                     window=window, scale=d ** -0.5,
                                     cap=cfg.logit_softcap)
    if bp_axes:
        out = attn._bp_constrain(out, mesh, bp_axes)
    y = linear(out.reshape(b, s, hh * d), p["wo"])
    cache = _write_kv_cache(k, v, qp, cache_size, window)
    return y, cache


def _mla_prefill(p, h, ctx, cfg, cache_size):
    b, s, _ = h.shape
    q_nope, q_rope, c_kv, k_rope = attn._mla_qkv(p, h, ctx["positions"], cfg)
    hh, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_head_dim
    k_nope = linear(c_kv, p["w_uk"]).reshape(b, s, hh, nope)
    v = linear(c_kv, p["w_uv"]).reshape(b, s, hh, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, hh, rope_d))], axis=-1)
    out = attn.blocked_attention(q, k, v, ctx["positions"], ctx["positions"],
                                 causal=True, window=0,
                                 scale=(nope + rope_d) ** -0.5)
    y = linear(out.reshape(b, s, hh * vd), p["wo"])
    pad = cache_size - s
    cache = {"c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
             "k_rope": jnp.pad(k_rope[:, :, 0], ((0, 0), (0, pad), (0, 0)))}
    return y, cache


def _scope(part: str) -> str:
    """Named scope of a block's mixer or FFN in the prefill and decode
    programs: ``attention`` for the attention mixers, else the part's own
    name (``mlp``, ``moe``, ``mamba2``, ...)."""
    return "attention" if part in ("attn", "swa", "mla") else part


def block_prefill(p, x, ctx, kind, cfg: ModelConfig, mesh, cache_size):
    mixer, ffn = kind.split("+")
    cache: dict = {}
    with jax.named_scope(_scope(mixer)):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if mixer in ("attn", "swa"):
            w = cfg.window_size if mixer == "swa" else 0
            y, cache["kv"] = _attn_prefill(p["attn"], h, ctx, cfg, w,
                                           cache_size)
        elif mixer == "mla":
            y, cache["kv"] = _mla_prefill(p["mla"], h, ctx, cfg, cache_size)
        elif mixer == "mamba2":
            y, cache["ssm"] = m2.mamba2_fwd(p["mamba"], h, cfg, None)
        elif mixer == "rwkv6":
            y, cache["tmix"] = rk.rwkv6_tmix_fwd(p["tmix"], h, cfg, None)
    x = x + y
    if cfg.is_encoder_decoder and ctx.get("enc_out") is not None:
        hc = rms_norm(x, p["norm_cross"], cfg.norm_eps)
        enc = ctx["enc_out"]
        b, se = enc.shape[:2]
        kvh, d = cfg.num_kv_heads, cfg.head_dim
        ck = linear(enc, p["cross"]["wk"]).reshape(b, se, kvh, d)
        cv = linear(enc, p["cross"]["wv"]).reshape(b, se, kvh, d)
        cache["cross"] = {"k": ck, "v": cv}
        y = attn.attention_fwd(p["cross"], hc, ctx["positions"], cfg,
                               causal=False, kv_x=enc)
        x = x + y
    aux = jnp.float32(0.0)
    if ffn != "none":
        with jax.named_scope(_scope(ffn)):
            y, aux, st = _ffn_fwd(p, x, ctx, ffn, cfg, mesh)
        if st is not None:
            cache["cmix"] = st
        x = x + y
    return x, aux, cache


# -- decode -------------------------------------------------------------------

def _cross_decode(p, x, cache, ctx, cfg):
    """Cross-attention at decode using precomputed encoder K/V."""
    b = x.shape[0]
    hh, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = rms_norm(x, p["norm_cross"], cfg.norm_eps)
    q = linear(h, p["cross"]["wq"]).reshape(b, 1, hh, d)
    g = hh // kvh
    qf = (q.reshape(b, kvh, g, d) * (d ** -0.5)).astype(jnp.float32)
    ck, cv = cache["k"], cache["v"]
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, ck.astype(jnp.float32))
    pr = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", pr, cv.astype(jnp.float32))
    out = out.reshape(b, 1, hh * d).astype(x.dtype)
    return linear(out, p["cross"]["wo"])


def block_decode(p, x, cache, index, ctx, kind, cfg: ModelConfig, mesh=None):
    mixer, ffn = kind.split("+")
    new_cache = dict(cache)
    with jax.named_scope(_scope(mixer)):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if mixer in ("attn", "swa"):
            w = cfg.window_size if mixer == "swa" else 0
            y, new_cache["kv"] = attn.attention_decode(
                p["attn"], h, cache["kv"], index, ctx["positions"], cfg,
                window=w)
        elif mixer == "mla":
            y, new_cache["kv"] = attn.mla_decode(
                p["mla"], h, cache["kv"], index, ctx["positions"], cfg)
        elif mixer == "mamba2":
            y, new_cache["ssm"] = m2.mamba2_decode(p["mamba"], h, cfg,
                                                   cache["ssm"])
        elif mixer == "rwkv6":
            y, new_cache["tmix"] = rk.rwkv6_tmix_fwd(p["tmix"], h, cfg,
                                                     cache["tmix"])
    x = x + y
    if cfg.is_encoder_decoder and "cross" in cache:
        x = x + _cross_decode(p, x, cache["cross"], ctx, cfg)
    if ffn != "none":
        with jax.named_scope(_scope(ffn)):
            hf = rms_norm(x, p["norm2"], cfg.norm_eps)
            if ffn == "mlp":
                y = mlp_fwd(p["mlp"], hf, cfg.mlp_kind)
            elif ffn == "moe":
                if mesh is not None:
                    y, _ = moe_fwd_ep(p["moe"], hf, cfg, mesh,
                                      ctx["data_axes"], ctx["model_axis"])
                else:
                    y, _ = moe_fwd(p["moe"], hf, cfg)
            elif ffn == "rwkv_cm":
                y, new_cache["cmix"] = rk.rwkv6_cmix_fwd(p["cmix"], hf, cfg,
                                                         cache["cmix"])
        x = x + y
    return x, new_cache


# ---------------------------------------------------------------------------
# Shared attention block (zamba2)
# ---------------------------------------------------------------------------

def shared_attn_fwd(p, x, ctx, cfg: ModelConfig):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    w = cfg.shared_attn_window
    y = attn.attention_fwd(p["attn"], h, ctx["positions"], cfg, window=w)
    x = x + y
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    return x + mlp_fwd(p["mlp"], h, cfg.mlp_kind)


def shared_attn_prefill(p, x, ctx, cfg: ModelConfig, cache_size):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    w = cfg.shared_attn_window
    y, kv = _attn_prefill(p["attn"], h, ctx, cfg, w, cache_size)
    x = x + y
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    return x + mlp_fwd(p["mlp"], h, cfg.mlp_kind), kv


def shared_attn_decode(p, x, kv, index, ctx, cfg: ModelConfig):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    w = cfg.shared_attn_window
    y, kv = attn.attention_decode(p["attn"], h, kv, index, ctx["positions"],
                                  cfg, window=w)
    x = x + y
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    return x + mlp_fwd(p["mlp"], h, cfg.mlp_kind), kv
