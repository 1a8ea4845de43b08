"""Parameter/cache PartitionSpec rules (logical-axis style, path-regex based).

Megatron-style tensor parallelism over "model" + ZeRO-3/FSDP over
("pod","data") for the large matrices.  Rules are matched against the
parameter path (first match wins) and the spec is right-aligned against the
array rank (stacked-layer leading dims get None).
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


FSDP = ("pod", "data")


# (path regex, spec over trailing dims)
PARAM_RULES: list[tuple[str, P]] = [
    # embeddings / heads
    (r"embed$", P("model", FSDP)),
    (r"pos_emb$", P(None, "model")),
    (r"head$", P(FSDP, "model")),
    # attention
    (r"attn/w[qkv]$", P(FSDP, "model")),
    (r"attn/wo$", P("model", FSDP)),
    (r"cross/w[qkv]$", P(FSDP, "model")),
    (r"cross/wo$", P("model", FSDP)),
    # MLA
    (r"mla/w_dq$", P(FSDP, None)),
    (r"mla/w_uq$", P(FSDP, "model")),
    (r"mla/w_dkv$", P(FSDP, None)),
    (r"mla/w_uk$", P(FSDP, "model")),
    (r"mla/w_uv$", P(FSDP, "model")),
    (r"mla/wo$", P("model", FSDP)),
    # dense MLP
    (r"mlp/w_(gate|up)$", P(FSDP, "model")),
    (r"mlp/w_down$", P("model", FSDP)),
    # MoE (experts over model, FSDP over d_model dim)
    (r"moe/w[gu]$", P("model", FSDP, None)),
    (r"moe/wd$", P("model", None, FSDP)),
    (r"moe/router$", P()),
    (r"moe/router_bias$", P()),
    (r"moe/shared/w_(gate|up)$", P(FSDP, "model")),
    (r"moe/shared/w_down$", P("model", FSDP)),
    # mamba2
    (r"mamba/w_zx$", P(FSDP, "model")),
    (r"mamba/w_bc$", P(FSDP, None)),
    (r"mamba/w_dt$", P(FSDP, "model")),
    (r"mamba/conv_x$", P(None, "model")),
    (r"mamba/conv_bc$", P()),
    (r"mamba/norm$", P("model")),
    (r"mamba/w_out$", P("model", FSDP)),
    # rwkv6
    (r"tmix/w_[rkvg]$", P(FSDP, "model")),
    (r"tmix/w_o$", P("model", FSDP)),
    (r"tmix/decay_b$", P(None, "model")),
    (r"tmix/decay_base$", P("model")),
    (r"tmix/bonus_u$", P("model", None)),
    (r"tmix/(ln_scale|ln_bias)$", P("model")),
    (r"cmix/w_k$", P(FSDP, "model")),
    (r"cmix/w_v$", P("model", FSDP)),
    (r"cmix/w_r$", P(FSDP, None)),
    # everything else (norm scales, mus, biases, loras): replicated
    (r".*", P()),
]

CACHE_RULES: list[tuple[str, P]] = [
    # KV caches: batch over data axes, heads over model (self-attention
    # caches are head-major (B, KV, S, D); cross caches are (B, S, KV, D))
    (r"kv/[kv]$", P(FSDP, "model", None, None)),
    (r"cross/[kv]$", P(FSDP, None, "model", None)),
    (r"shared.*/[kv]$", P(FSDP, "model", None, None)),
    # MLA latent cache: batch over data only (latent dim small)
    (r"kv/c_kv$", P(FSDP, None, None)),
    (r"kv/k_rope$", P(FSDP, None, None)),
    # SSM / RWKV states: batch over data, heads/channels over model
    (r"ssm/conv_x$", P(FSDP, None, "model")),
    (r"ssm/conv_bc$", P(FSDP, None, None)),
    (r"ssm/h$", P(FSDP, "model", None, None)),
    (r"tmix/shift$", P(FSDP, "model")),
    (r"tmix/wkv$", P(FSDP, "model", None, None)),
    (r"cmix/shift$", P(FSDP, "model")),
    (r"index$", P()),
    (r".*", P()),
]

BATCH_RULES: list[tuple[str, P]] = [
    (r"(tokens|labels|token)$", P(FSDP, None)),
    (r"prefix_embeds$", P(FSDP, None, None)),
    (r"enc_embeds$", P(FSDP, None, None)),
    (r"mrope_positions$", P(None, FSDP, None)),
    (r".*", P()),
]


def _match(path: str, rules) -> P:
    for pat, spec in rules:
        if re.search(pat, path):
            return spec
    return P()


def _fit_spec(spec: P, ndim: int, shape, mesh) -> P:
    """Right-align spec to ndim; drop axes that don't divide the dim."""
    entries = list(spec)
    if len(entries) > ndim:
        entries = entries[-ndim:]
    entries = [None] * (ndim - len(entries)) + entries
    fixed = []
    for dim, ax in zip(shape, entries):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in mesh.shape)
        n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if n <= 1 or dim % n != 0:
            # try a prefix of the axes tuple that divides
            while axes and (dim % int(np.prod([mesh.shape[a] for a in axes]))):
                axes = axes[:-1]
            if not axes:
                fixed.append(None)
                continue
        fixed.append(axes if len(axes) > 1 else axes[0])
    return P(*fixed)


def _specs_for(tree: Any, rules, mesh) -> Any:
    from repro.utils.tree import tree_map_with_path

    def fn(path, leaf):
        spec = _match(path, rules)
        return _fit_spec(spec, leaf.ndim, leaf.shape, mesh)

    return tree_map_with_path(fn, tree)


def param_specs(params_shape: Any, mesh, fsdp: bool = True) -> Any:
    """PartitionSpec pytree for a params (shape) pytree.

    fsdp=False (serving): drop the ("pod","data") ZeRO-3 axes from all
    non-expert params so decode steps do not all-gather weights every token
    (EXPERIMENTS.md §Perf, rwkv6 decode iteration).  MoE expert weights keep
    their two-axis sharding — the partial-sum EP path consumes them
    in place (moe_partial_ep)."""
    from repro.utils.tree import tree_map_with_path

    def fn(path, leaf):
        spec = _match(path, PARAM_RULES)
        if not fsdp and not re.search(r"moe/w[gud]$", path):
            spec = P(*[tuple(a for a in (ax if isinstance(ax, tuple)
                                         else (ax,)) if a not in FSDP) or None
                       if ax is not None else None for ax in spec])
            spec = P(*[ax[0] if isinstance(ax, tuple) and len(ax) == 1
                       else (None if isinstance(ax, tuple) and not ax else ax)
                       for ax in spec])
        return _fit_spec(spec, leaf.ndim, leaf.shape, mesh)

    return tree_map_with_path(fn, params_shape)


# decode-tuned cache rules: the cache SEQUENCE dim shards over "model", so
# each rank reads 1/n_model of the cache and the softmax reduces via a tiny
# all-reduce (EXPERIMENTS.md §Perf kimi decode iteration 2).  The in-place
# cache write (dynamic-update-slice at a traced index) stays local — GSPMD
# partitions DUS on a sharded dim without gathering (verified in the perf
# log).  Head-dim sharding is dropped (kv heads rarely divide 16).
CACHE_RULES_SEQSHARD: list[tuple[str, P]] = [
    (r"kv/[kv]$", P(FSDP, None, "model", None)),
    (r"cross/[kv]$", P(FSDP, "model", None, None)),
    (r"shared.*/[kv]$", P(FSDP, None, "model", None)),
    (r"kv/c_kv$", P(FSDP, "model", None)),
    (r"kv/k_rope$", P(FSDP, "model", None)),
] + CACHE_RULES[5:]


def cache_specs(cache_shape: Any, mesh, seq_shard: bool = False) -> Any:
    rules = CACHE_RULES_SEQSHARD if seq_shard else CACHE_RULES
    return _specs_for(cache_shape, rules, mesh)


def batch_specs(batch_shape: Any, mesh) -> Any:
    return _specs_for(batch_shape, BATCH_RULES, mesh)


def shardings(spec_tree: Any, mesh) -> Any:
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
