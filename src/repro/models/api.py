"""Public model API: build_model(cfg) -> Model with init/forward/prefill/decode.

All functions are pure; params and caches are pytrees.  ``Model`` is a thin
namespace so the functions close over the config (hashable, frozen) and an
optional mesh for the expert-parallel MoE path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba2 as m2
from repro.models import rwkv6 as rk
from repro.models import transformer as tfm
from repro.models.common import embed_init, dense_init, linear, rms_norm, to_dtype

MAX_LEARNED_POS = 32768


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stacked_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(key, cfg: ModelConfig):
    dtype = to_dtype(cfg.param_dtype)
    ks = iter(jax.random.split(key, 16))
    p: dict = {
        "embed": embed_init(next(ks), (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(next(ks), (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.rope_kind == "learned":
        p["pos_emb"] = embed_init(next(ks), (MAX_LEARNED_POS, cfg.d_model), dtype)
    groups = []
    for kind, n in tfm.layer_groups(cfg):
        groups.append(_stacked_init(
            next(ks), n, lambda k, kind=kind: tfm.init_block(k, cfg, kind, dtype)))
    p["groups"] = tuple(groups)
    if cfg.shared_attn_every:
        p["shared_attn"] = tfm.init_shared_attn(next(ks), cfg, dtype)
    if cfg.is_encoder_decoder:
        enc_groups = _stacked_init(
            next(ks), cfg.encoder_layers,
            lambda k: tfm.init_block(k, dataclasses.replace(
                cfg, is_encoder_decoder=False), "attn+mlp", dtype))
        p["encoder"] = {
            "groups": (enc_groups,),
            "pos_emb": embed_init(next(ks), (cfg.encoder_seq_len, cfg.d_model),
                                  dtype),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
    if cfg.mtp_depth:
        last_kind = cfg.blocks[-1]
        p["mtp"] = {
            "proj": dense_init(next(ks), (2 * cfg.d_model, cfg.d_model), dtype),
            "norm_h": jnp.zeros((cfg.d_model,), dtype),
            "norm_e": jnp.zeros((cfg.d_model,), dtype),
            "block": tfm.init_block(next(ks), cfg, last_kind, dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens, positions=None):
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.rope_kind == "learned" and positions is not None:
        x = x + params["pos_emb"][positions]
    return x


def _head(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        return linear(x, params["embed"].T)
    return linear(x, params["head"])


def _dp_axes(mesh) -> tuple:
    if mesh is None:
        return ("data",)
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _constrain(x, mesh, spec):
    if mesh is None:
        return x
    fixed = jax.sharding.PartitionSpec(
        *[(tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                 if a in mesh.axis_names) or None) if ax is not None else None
          for ax in spec])
    fixed = jax.sharding.PartitionSpec(
        *[ax[0] if isinstance(ax, tuple) and len(ax) == 1 else ax
          for ax in fixed])
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, fixed))


# ---------------------------------------------------------------------------
# Group execution (scan-over-layers)
# ---------------------------------------------------------------------------

def _layer_slice(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _run_groups_fwd(params, x, ctx, cfg: ModelConfig, mesh,
                    groups=None, enc_mode=False):
    """Train-mode stack: no caches. Returns (x, aux)."""
    gspec = tfm.layer_groups(cfg) if not enc_mode else [
        ("attn+mlp", cfg.encoder_layers)]
    gparams = params["groups"] if groups is None else groups
    shared = params.get("shared_attn") if not enc_mode else None
    every = cfg.shared_attn_every
    aux = jnp.float32(0.0)
    layer0 = 0
    for (kind, n), gp in zip(gspec, gparams):
        def body(carry, xs):
            xc, auxc = carry
            pl, idx = xs
            if shared is not None and every:
                xc = jax.lax.cond(
                    idx % every == 0,
                    lambda v: tfm.shared_attn_fwd(shared, v, ctx, cfg),
                    lambda v: v, xc)
            xc, a = tfm.block_fwd(pl, xc, ctx, kind, cfg, mesh)
            return (xc, auxc + a), None

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        idxs = jnp.arange(layer0, layer0 + n)
        if cfg.scan_layers and n > 1:
            (x, aux), _ = jax.lax.scan(body, (x, aux), (gp, idxs))
        else:
            for i in range(n):
                (x, aux), _ = body((x, aux), (_layer_slice(gp, i), idxs[i]))
        layer0 += n
    return x, aux


def _run_groups_prefill(params, x, ctx, cfg: ModelConfig, mesh, cache_size):
    gspec = tfm.layer_groups(cfg)
    shared = params.get("shared_attn")
    every = cfg.shared_attn_every
    aux = jnp.float32(0.0)
    layer0 = 0
    group_caches = []
    shared_kv = _init_shared_cache(cfg, x.shape[0], cache_size,
                                   to_dtype(cfg.dtype)) if shared else None
    for (kind, n), gp in zip(gspec, params["groups"]):
        def body(carry, xs):
            xc, auxc, skv = carry
            pl, idx = xs
            if shared is not None and every:
                def apply(v_skv):
                    v, skv_in = v_skv
                    app = idx // every
                    v2, kv = tfm.shared_attn_prefill(shared, v, ctx, cfg,
                                                     cache_size)
                    skv_out = jax.tree.map(
                        lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                            buf, new.astype(buf.dtype), app, 0),
                        skv_in, kv)
                    return v2, skv_out
                xc, skv = jax.lax.cond(idx % every == 0, apply,
                                       lambda v_skv: v_skv, (xc, skv))
            xc, a, cache = tfm.block_prefill(pl, xc, ctx, kind, cfg, mesh,
                                             cache_size)
            return (xc, auxc + a, skv), cache

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        idxs = jnp.arange(layer0, layer0 + n)
        if cfg.scan_layers and n > 1:
            (x, aux, shared_kv), caches = jax.lax.scan(
                body, (x, aux, shared_kv), (gp, idxs))
        else:
            caches_list = []
            for i in range(n):
                (x, aux, shared_kv), c = body((x, aux, shared_kv),
                                              (_layer_slice(gp, i), idxs[i]))
                caches_list.append(c)
            caches = jax.tree.map(lambda *a: jnp.stack(a), *caches_list)
        group_caches.append(caches)
        layer0 += n
    return x, aux, tuple(group_caches), shared_kv


def _run_groups_decode(params, x, cache, index, ctx, cfg: ModelConfig,
                       mesh=None):
    gspec = tfm.layer_groups(cfg)
    shared = params.get("shared_attn")
    every = cfg.shared_attn_every
    layer0 = 0
    new_group_caches = []
    shared_kv = cache.get("shared")
    for (kind, n), gp, gc in zip(gspec, params["groups"], cache["groups"]):
        def body(carry, xs):
            xc, skv = carry
            pl, cl, idx = xs
            if shared is not None and every:
                def apply(v_skv):
                    v, skv_in = v_skv
                    app = idx // every
                    kv = jax.tree.map(lambda a: a[app], skv_in)
                    v2, kv2 = tfm.shared_attn_decode(shared, v, kv, index,
                                                     ctx, cfg)
                    skv_out = jax.tree.map(
                        lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                            buf, new.astype(buf.dtype), app, 0),
                        skv_in, kv2)
                    return v2, skv_out
                xc, skv = jax.lax.cond(idx % every == 0, apply,
                                       lambda v_skv: v_skv, (xc, skv))
            xc, new_cl = tfm.block_decode(pl, xc, cl, index, ctx, kind, cfg,
                                          mesh)
            return (xc, skv), new_cl

        idxs = jnp.arange(layer0, layer0 + n)
        if cfg.scan_layers and n > 1:
            (x, shared_kv), new_gc = jax.lax.scan(body, (x, shared_kv),
                                                  (gp, gc, idxs))
        else:
            ncs = []
            for i in range(n):
                (x, shared_kv), nc = body(
                    (x, shared_kv),
                    (_layer_slice(gp, i), _layer_slice(gc, i), idxs[i]))
                ncs.append(nc)
            new_gc = jax.tree.map(lambda *a: jnp.stack(a), *ncs)
        new_group_caches.append(new_gc)
        layer0 += n
    new_cache = dict(cache)
    new_cache["groups"] = tuple(new_group_caches)
    if shared_kv is not None:
        new_cache["shared"] = shared_kv
    return x, new_cache


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                      dtype):
    mixer, ffn = kind.split("+")
    c: dict = {}
    if mixer == "attn":
        c["kv"] = attn_mod.init_attention_cache(cfg, batch, cache_len, dtype)
    elif mixer == "swa":
        c["kv"] = attn_mod.init_attention_cache(cfg, batch, cache_len, dtype,
                                                window=cfg.window_size)
    elif mixer == "mla":
        c["kv"] = attn_mod.init_mla_cache(cfg, batch, cache_len, dtype)
    elif mixer == "mamba2":
        c["ssm"] = m2.init_mamba2_state(cfg, batch, dtype)
    elif mixer == "rwkv6":
        st = rk.init_rwkv6_state(cfg, batch, dtype)
        c["tmix"] = st["tmix"]
    if ffn == "rwkv_cm":
        st = rk.init_rwkv6_state(cfg, batch, dtype)
        c["cmix"] = st["cmix"]
    if cfg.is_encoder_decoder:
        c["cross"] = {
            "k": jnp.zeros((batch, cfg.encoder_seq_len, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((batch, cfg.encoder_seq_len, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
        }
    return c


def _init_shared_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    n_apps = (cfg.num_layers + cfg.shared_attn_every - 1) // cfg.shared_attn_every
    w = cfg.shared_attn_window or cache_len
    single = attn_mod.init_attention_cache(cfg, batch, cache_len, dtype,
                                           window=w)
    return jax.tree.map(lambda a: jnp.zeros((n_apps,) + a.shape, a.dtype),
                        single)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    dtype = to_dtype(cfg.dtype)
    groups = []
    for kind, n in tfm.layer_groups(cfg):
        single = _init_block_cache(cfg, kind, batch, cache_len, dtype)
        groups.append(jax.tree.map(
            lambda a: jnp.zeros((n,) + a.shape, a.dtype), single))
    cache = {"groups": tuple(groups), "index": jnp.zeros((), jnp.int32)}
    if cfg.shared_attn_every:
        cache["shared"] = _init_shared_cache(cfg, batch, cache_len, dtype)
    return cache


# ---------------------------------------------------------------------------
# Top-level steps
# ---------------------------------------------------------------------------

def _positions_for(cfg: ModelConfig, batch: dict, b: int, s: int):
    if cfg.rope_kind == "mrope":
        return batch["mrope_positions"]
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def _encoder_fwd(params, cfg: ModelConfig, enc_embeds, mesh):
    enc = params["encoder"]
    b, s, _ = enc_embeds.shape
    x = enc_embeds + enc["pos_emb"][None, :s]
    ctx = {"positions": jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)),
           "causal": False, "enc_out": None, "mesh": mesh,
           "data_axes": _dp_axes(mesh), "model_axis": "model"}
    ecfg = dataclasses.replace(cfg, is_encoder_decoder=False,
                               rope_kind="none", shared_attn_every=0)
    x, _ = _run_groups_fwd({"groups": enc["groups"]}, x, ctx, ecfg, mesh,
                           groups=enc["groups"], enc_mode=True)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def _assemble_inputs(params, cfg: ModelConfig, batch, mesh):
    """Returns (x, ctx, b, s_total)."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    if cfg.num_patch_tokens and "prefix_embeds" in batch:
        s_total = tokens.shape[1] + batch["prefix_embeds"].shape[1]
    else:
        s_total = tokens.shape[1]
    positions = _positions_for(cfg, batch, b, s_total)
    tok_positions = positions if cfg.rope_kind != "mrope" else None
    if cfg.num_patch_tokens and "prefix_embeds" in batch:
        pe = batch["prefix_embeds"].astype(to_dtype(cfg.dtype))
        te = _embed(params, cfg, tokens,
                    None if tok_positions is None else
                    tok_positions[:, pe.shape[1]:])
        x = jnp.concatenate([pe, te], axis=1)
    else:
        x = _embed(params, cfg, tokens, tok_positions)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encoder_fwd(params, cfg,
                               batch["enc_embeds"].astype(to_dtype(cfg.dtype)),
                               mesh)
    ctx = {"positions": positions, "enc_out": enc_out, "causal": True,
           "mesh": mesh, "data_axes": _dp_axes(mesh), "model_axis": "model"}
    return x, ctx, b, s_total


def forward(params, batch: dict, cfg: ModelConfig, mesh=None):
    """Full-sequence forward (training).  Returns (logits, aux_loss)."""
    x, ctx, b, s = _assemble_inputs(params, cfg, batch, mesh)
    x = _constrain(x, mesh, jax.sharding.PartitionSpec(("pod", "data"), None, None))
    x, aux = _run_groups_fwd(params, x, ctx, cfg, mesh)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, x)
    logits = _constrain(logits, mesh,
                        jax.sharding.PartitionSpec(("pod", "data"), None, "model"))
    return logits, aux


def forward_hidden(params, batch: dict, cfg: ModelConfig, mesh=None):
    """Like forward but returns pre-final-norm hidden states (for MTP)."""
    x, ctx, _, _ = _assemble_inputs(params, cfg, batch, mesh)
    x, aux = _run_groups_fwd(params, x, ctx, cfg, mesh)
    return x, aux


def mtp_logits(params, hidden, tokens, cfg: ModelConfig, mesh=None):
    """DeepSeek-V3 multi-token prediction head (depth 1): from hidden state
    h_t and the embedding of token t+1, predict token t+2."""
    p = params["mtp"]
    h = rms_norm(hidden[:, :-1], p["norm_h"], cfg.norm_eps)
    e = rms_norm(_embed(params, cfg, tokens[:, 1:]), p["norm_e"], cfg.norm_eps)
    z = linear(jnp.concatenate([h, e], axis=-1), p["proj"])
    b, s, _ = z.shape
    ctx = {"positions": jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)),
           "enc_out": None, "causal": True, "mesh": mesh,
           "data_axes": _dp_axes(mesh), "model_axis": "model"}
    z, aux = tfm.block_fwd(p["block"], z, ctx, cfg.blocks[-1], cfg, mesh)
    z = rms_norm(z, params["final_norm"], cfg.norm_eps)
    return _head(params, cfg, z), aux


def prefill(params, batch: dict, cfg: ModelConfig, mesh=None,
            cache_len: Optional[int] = None):
    """Process the whole prompt; returns (last_logits, cache)."""
    with jax.named_scope("embed"):
        x, ctx, b, s = _assemble_inputs(params, cfg, batch, mesh)
    x = _constrain(x, mesh, jax.sharding.PartitionSpec(("pod", "data"), None, None))
    cache_len = cache_len or s
    x, aux, group_caches, shared_kv = _run_groups_prefill(
        params, x, ctx, cfg, mesh, cache_len)
    with jax.named_scope("lm_head"):
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = _head(params, cfg, x)
    cache = {"groups": group_caches,
             "index": jnp.asarray(s, jnp.int32)}
    if shared_kv is not None:
        cache["shared"] = shared_kv
    return logits[:, 0], cache


def decode_step(params, cache: dict, token, cfg: ModelConfig, mesh=None,
                mrope_positions=None):
    """One serve step: one new token per sequence against the cache.

    token: (B, 1) int32.  Returns (logits (B, V), new_cache)."""
    index = cache["index"]
    b = token.shape[0]
    if cfg.rope_kind == "mrope":
        positions = (mrope_positions if mrope_positions is not None
                     else jnp.broadcast_to(index, (3, b, 1)).astype(jnp.int32))
    else:
        positions = jnp.broadcast_to(index, (b, 1)).astype(jnp.int32)
    tok_positions = positions if cfg.rope_kind != "mrope" else None
    with jax.named_scope("embed"):
        x = _embed(params, cfg, token, tok_positions)
    ctx = {"positions": positions, "enc_out": None, "causal": True,
           "mesh": mesh, "data_axes": _dp_axes(mesh), "model_axis": "model"}
    x, new_cache = _run_groups_decode(params, x, cache, index, ctx, cfg, mesh)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _head(params, cfg, x)
    new_cache["index"] = index + 1
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Model namespace + input specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    mesh: Any = None

    def init(self, key):
        return init_params(key, self.cfg)

    def forward(self, params, batch):
        return forward(params, batch, self.cfg, self.mesh)

    def forward_hidden(self, params, batch):
        return forward_hidden(params, batch, self.cfg, self.mesh)

    def mtp_logits(self, params, hidden, tokens):
        return mtp_logits(params, hidden, tokens, self.cfg, self.mesh)

    def prefill(self, params, batch, cache_len=None):
        return prefill(params, batch, self.cfg, self.mesh, cache_len)

    def decode_step(self, params, cache, token, mrope_positions=None):
        return decode_step(params, cache, token, self.cfg, self.mesh,
                           mrope_positions)

    def init_cache(self, batch: int, cache_len: int):
        return init_cache(self.cfg, batch, cache_len)


def build_model(cfg: ModelConfig, mesh=None) -> Model:
    return Model(cfg=cfg, mesh=mesh)


def input_specs(cfg: ModelConfig, shape: InputShape | str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a given shape.

    The modality frontends are stubs per the assignment carve-out: audio
    supplies (B, encoder_seq_len, d) frame embeddings, VLM supplies
    (B, num_patch_tokens, d) patch embeddings.
    """
    if isinstance(shape, str):
        shape = INPUT_SHAPES[shape]
    b, s = shape.global_batch, shape.seq_len
    f32 = to_dtype(cfg.dtype)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    specs: dict = {}
    if shape.kind in ("train", "prefill"):
        s_text = s
        if cfg.num_patch_tokens:
            s_text = s - cfg.num_patch_tokens
            specs["prefix_embeds"] = sds((b, cfg.num_patch_tokens,
                                          cfg.d_model), f32)
            specs["mrope_positions"] = sds((3, b, s), i32)
        specs["tokens"] = sds((b, s_text), i32)
        if shape.kind == "train":
            specs["labels"] = sds((b, s_text), i32)
        if cfg.is_encoder_decoder:
            specs["enc_embeds"] = sds((b, cfg.encoder_seq_len, cfg.d_model),
                                      f32)
    else:  # decode
        specs["token"] = sds((b, 1), i32)
        if cfg.rope_kind == "mrope":
            specs["mrope_positions"] = sds((3, b, 1), i32)
    return specs
