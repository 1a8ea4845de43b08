"""Mixture-of-Experts layer with expert-parallel shard_map execution.

Two execution paths share one sort-based capacity dispatch core:

* ``moe_fwd`` — single-device / GSPMD path (smoke tests, tiny token counts).
* ``moe_fwd_ep`` — production path under ``jax.shard_map``: tokens sharded
  over the ("pod", "data") axes, experts sharded over "model", expert weights
  additionally FSDP-sharded over ("pod", "data") on the d_model dim and
  all-gathered inside the shard (ZeRO-3 style).  Each model rank dispatches
  its data shard's tokens to its local experts (no token all-to-all needed in
  the replicated-activation scheme); outputs are combined with a psum over
  "model".  See DESIGN.md §5.

Dispatch is sort-based (argsort by expert id + capacity clamp) instead of the
GShard one-hot einsum, so the dispatch tensor is O(T·k) indices rather than
O(T·E·C) one-hots — the difference between 587 MB and 4 GB per device at the
prefill_32k shape.

Routing supports softmax top-k (classic) and the DeepSeek-V3 sigmoid scoring
with a bias-balanced, aux-loss-free flavor (bias buffer held in params but
updated outside the gradient), plus the standard load-balance aux loss.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Array, dense_init
from repro.models.mlp import init_mlp, mlp_fwd


def init_moe(key, cfg: ModelConfig, dtype):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "router_bias": jnp.zeros((e,), jnp.float32),   # aux-free balance buffer
        "wg": dense_init(ks[1], (e, d, f), dtype),
        "wu": dense_init(ks[2], (e, d, f), dtype),
        "wd": dense_init(ks[3], (e, f, d), dtype, fan_in=f),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], d, f * cfg.num_shared_experts,
                               "swiglu", dtype)
    return p


def route_topk(logits: Array, bias: Array, k: int, kind: str):
    """Returns (weights (T,k), ids (T,k), probs (T,E)) for aux loss."""
    if kind == "sigmoid":  # DeepSeek-V3: sigmoid scores, bias only for topk
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, ids = jax.lax.top_k(scores + bias[None, :], k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        w, ids = jax.lax.top_k(probs, k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, ids, probs


def load_balance_aux(probs: Array, ids: Array, num_experts: int) -> Array:
    """GShard/Switch aux loss: E * sum_i f_i * P_i (local-batch estimate)."""
    t = probs.shape[0]
    f = jnp.zeros((num_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(t * ids.shape[1], 1)
    p = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p)


def capacity_for(tokens: int, k: int, num_experts: int, cf: float) -> int:
    """Static per-shard expert capacity.  Small token counts (decode) get a
    zero-drop floor; large counts get the classic cf-scaled capacity."""
    c = int(math.ceil(tokens * k * cf / num_experts))
    c = max(c, min(tokens * k, 8))
    c = min(c, tokens * k)
    return int(math.ceil(c / 4) * 4) if c > 8 else c


def _expert_ffn(wg: Array, wu: Array, wd: Array, xb: Array) -> Array:
    """Batched expert SwiGLU: xb (E, C, d) -> (E, C, d)."""
    h = jnp.einsum("ecd,edf->ecf", xb, wg,
                   preferred_element_type=jnp.float32).astype(xb.dtype)
    u = jnp.einsum("ecd,edf->ecf", xb, wu,
                   preferred_element_type=jnp.float32).astype(xb.dtype)
    a = jax.nn.silu(h) * u
    return jnp.einsum("ecf,efd->ecd", a, wd,
                      preferred_element_type=jnp.float32).astype(xb.dtype)


def _dispatch_compute_combine(x: Array, ids: Array, w: Array, wg, wu, wd,
                              capacity: int, e_lo: int, e_local: int) -> Array:
    """Sort-based capacity dispatch -> expert FFN -> weighted combine.

    x: (T, d); ids/w: (T, k) with GLOBAL expert ids; computes only experts in
    [e_lo, e_lo + e_local) (pass 0, E for the non-EP path).  Returns the
    partial output (T, d) (zero contribution for non-local / dropped pairs).
    """
    t, d = x.shape
    k = ids.shape[1]
    flat_ids = ids.reshape(-1)
    flat_w = w.reshape(-1).astype(jnp.float32)
    local = (flat_ids >= e_lo) & (flat_ids < e_lo + e_local)
    lids = jnp.clip(flat_ids - e_lo, 0, e_local - 1)

    order = jnp.argsort(jnp.where(local, lids, e_local), stable=True)
    sid = lids[order]
    s_local = local[order]
    s_w = flat_w[order]
    s_tok = order // k

    counts = jnp.zeros((e_local,), jnp.int32).at[lids].add(local.astype(jnp.int32))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k, dtype=jnp.int32) - offsets[sid]
    keep = s_local & (pos < capacity)
    trash = e_local * capacity
    slot = jnp.where(keep, sid * capacity + pos, trash)

    buf = jnp.zeros((e_local * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(x[s_tok], mode="drop")
    xb = buf[:-1].reshape(e_local, capacity, d)

    yb = _expert_ffn(wg, wu, wd, xb).reshape(e_local * capacity, d)
    contrib = yb[jnp.minimum(slot, trash - 1)].astype(jnp.float32)
    contrib = contrib * (s_w * keep.astype(jnp.float32))[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[s_tok].add(contrib)
    return y.astype(x.dtype)


def moe_fwd(params, x: Array, cfg: ModelConfig):
    """Single-shard MoE (reference / smoke / tiny-token path).

    x: (B, S, d).  Returns (y, aux_loss).
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = xt.astype(jnp.float32) @ params["router"]
    w, ids, probs = route_topk(logits, params["router_bias"],
                               cfg.num_experts_per_tok, cfg.moe_router_kind)
    aux = load_balance_aux(probs, ids, cfg.num_experts)
    cap = capacity_for(b * s, cfg.num_experts_per_tok, cfg.num_experts,
                       cfg.moe_capacity_factor)
    y = _dispatch_compute_combine(xt, ids, w, params["wg"], params["wu"],
                                  params["wd"], cap, 0, cfg.num_experts)
    if "shared" in params:
        y = y + mlp_fwd(params["shared"], xt, "swiglu")
    return y.reshape(b, s, d), aux


PARTIAL_EP_MAX_TOKENS = 4096


def moe_fwd_ep(params, x: Array, cfg: ModelConfig, mesh: jax.sharding.Mesh,
               data_axes: tuple, model_axis: str):
    """Expert-parallel MoE under shard_map.  x: (B, S, d) with B sharded over
    ``data_axes``.  Returns (y, aux_loss)."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    n_model = mesh.shape[model_axis]
    e_local = cfg.num_experts // n_model
    if (cfg.moe_partial_ep and b * s <= PARTIAL_EP_MAX_TOKENS
            and d % n_data == 0):
        return _moe_fwd_partial_ep(params, x, cfg, mesh, data_axes,
                                   model_axis)
    t_local = (b * s) // n_data
    cap = capacity_for(t_local, cfg.num_experts_per_tok, cfg.num_experts,
                       cfg.moe_capacity_factor)

    def shard_fn(xt, router, router_bias, wg, wu, wd):
        # xt: (T_local, d); wg/wu/wd: (E_local, d/n_data, f) -> FSDP gather
        wg = jax.lax.all_gather(wg, data_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, data_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, data_axes, axis=2, tiled=True)
        logits = xt.astype(jnp.float32) @ router
        w, ids, probs = route_topk(logits, router_bias,
                                   cfg.num_experts_per_tok, cfg.moe_router_kind)
        aux = load_balance_aux(probs, ids, cfg.num_experts)
        aux = jax.lax.pmean(aux, data_axes)
        e_lo = jax.lax.axis_index(model_axis) * e_local
        y = _dispatch_compute_combine(xt, ids, w, wg, wu, wd, cap,
                                      e_lo, e_local)
        y = jax.lax.psum(y, model_axis)
        return y, aux

    xt = x.reshape(b * s, d)
    dspec = P(data_axes, None)
    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(dspec, P(), P(), P(model_axis, data_axes, None),
                  P(model_axis, data_axes, None), P(model_axis, None, data_axes)),
        out_specs=(dspec, P()),
        check_vma=False,
    )(xt, params["router"], params["router_bias"],
      params["wg"], params["wu"], params["wd"])
    if "shared" in params:
        y = y + mlp_fwd(params["shared"], xt, "swiglu")
    return y.reshape(b, s, d), aux


def _moe_fwd_partial_ep(params, x: Array, cfg: ModelConfig, mesh,
                        data_axes: tuple, model_axis: str):
    """Serving-path MoE: d-sliced partial-sum expert compute.

    The FSDP gather in the training path moves the FULL expert weight set
    over ICI every step — fatal at decode (kimi-k2: ~6 GB/layer gathered to
    serve 8 local tokens; see EXPERIMENTS.md §Perf).  Here every chip keeps
    its resident (E/n_model, d/n_data, f) weight slice and computes partial
    matmuls over its d-slice; the tiny token activations move instead:

        all-gather tokens over data  (T x d, ~2 MB at decode_32k)
        partial h/u = x_slice @ w_slice ; psum over data
        y_slice = a @ wd_slice        ; psum over model + gather d over data

    Collective volume per layer drops from O(E d f / n_data) to O(T d + E_l
    C f) — weights never move.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    t = b * s
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    n_model = mesh.shape[model_axis]
    e_local = cfg.num_experts // n_model
    d_shard = d // n_data
    t_local = t // n_data
    cap = capacity_for(t, cfg.num_experts_per_tok, cfg.num_experts,
                       cfg.moe_capacity_factor)

    def shard_fn(xt_local, router, router_bias, wg, wu, wd):
        # xt_local: (T_local, d); w*: (E_local, d_shard, f) resident slices
        xt = jax.lax.all_gather(xt_local, data_axes, axis=0, tiled=True)
        logits = xt.astype(jnp.float32) @ router
        w, ids, probs = route_topk(logits, router_bias,
                                   cfg.num_experts_per_tok,
                                   cfg.moe_router_kind)
        aux = load_balance_aux(probs, ids, cfg.num_experts)
        e_lo = jax.lax.axis_index(model_axis) * e_local
        # data-rank index (possibly over a ("pod","data") tuple)
        didx = jnp.int32(0)
        stride = 1
        for a in reversed(data_axes):
            didx = didx + jax.lax.axis_index(a) * stride
            stride *= mesh.shape[a]

        # dispatch in full-d, then slice this rank's d range
        k = ids.shape[1]
        flat_ids = ids.reshape(-1)
        flat_w = w.reshape(-1).astype(jnp.float32)
        local = (flat_ids >= e_lo) & (flat_ids < e_lo + e_local)
        lids = jnp.clip(flat_ids - e_lo, 0, e_local - 1)
        order = jnp.argsort(jnp.where(local, lids, e_local), stable=True)
        sid = lids[order]
        s_local = local[order]
        s_w = flat_w[order]
        s_tok = order // k
        counts = jnp.zeros((e_local,), jnp.int32).at[lids].add(
            local.astype(jnp.int32))
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(t * k, dtype=jnp.int32) - offsets[sid]
        keep = s_local & (pos < cap)
        trash = e_local * cap
        slot = jnp.where(keep, sid * cap + pos, trash)
        x_sliced = jax.lax.dynamic_slice_in_dim(xt, didx * d_shard, d_shard,
                                                axis=1)
        buf = jnp.zeros((e_local * cap + 1, d_shard), xt.dtype)
        buf = buf.at[slot].set(x_sliced[s_tok], mode="drop")
        xb = buf[:-1].reshape(e_local, cap, d_shard)

        h = jnp.einsum("ecd,edf->ecf", xb, wg,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", xb, wu,
                       preferred_element_type=jnp.float32)
        h = jax.lax.psum(h, data_axes)
        u = jax.lax.psum(u, data_axes)
        a = (jax.nn.silu(h) * u).astype(xt.dtype)
        # wd stored (E_local, f, d) sharded over data on the LAST dim
        yb = jnp.einsum("ecf,efd->ecd", a, wd,
                        preferred_element_type=jnp.float32)  # (E_l,C,d_shard)
        yb = yb.reshape(e_local * cap, d_shard)
        contrib = yb[jnp.minimum(slot, trash - 1)]
        contrib = contrib * (s_w * keep.astype(jnp.float32))[:, None]
        y_slice = jnp.zeros((t, d_shard), jnp.float32).at[s_tok].add(contrib)
        y_slice = jax.lax.psum(y_slice, model_axis)
        y_full = jax.lax.all_gather(y_slice, data_axes, axis=1, tiled=True)
        y_mine = jax.lax.dynamic_slice_in_dim(y_full, didx * t_local,
                                              t_local, axis=0)
        return y_mine.astype(xt.dtype), aux

    xt = x.reshape(t, d)
    dspec = P(data_axes, None)
    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(dspec, P(), P(), P(model_axis, data_axes, None),
                  P(model_axis, data_axes, None),
                  P(model_axis, None, data_axes)),
        out_specs=(dspec, P()),
        check_vma=False,
    )(xt, params["router"], params["router_bias"],
      params["wg"], params["wu"], params["wd"])
    if "shared" in params:
        y = y + mlp_fwd(params["shared"], xt, "swiglu")
    return y.reshape(b, s, d), aux
