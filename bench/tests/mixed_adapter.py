"""A test-only adapter (not a benchmark configuration): a decoder whose
layers mix windowed and full attention, ``swa+mlp``, ``attn+mlp``,
``swa+mlp``, so the program holds two kinds of layer in three groups.

The prefill kernel runs in every layer; the decode kernel only in the full
layer, since windowed decode has no kernel.  So each kernel's layers differ
from the depth, and a harness or metric that still counted
``num_hidden_layers`` would read wrong.  It enters through the same files
as any architecture: this module, a configuration dict and the reference
(``decoder_reference``, which it matches while every sequence fits the
window).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from itertools import groupby

import jax

from bench import weights
from bench.configs import decoder_adapter
from bench.counts.kernels import causal_pairs
from bench.counts.model import layer_matmul_params

PATTERN = ("swa+mlp", "attn+mlp", "swa+mlp")


def program_config(cfg: dict):
    return dataclasses.replace(decoder_adapter.program_config(cfg),
                               blocks=PATTERN)


def _groups() -> tuple:
    out, first = [], 0
    for _, run in groupby(PATTERN):
        n = len(list(run))
        out.append((first, n))
        first += n
    return tuple(out)


@partial(jax.jit, static_argnums=(1,))
def _tree(key, sizes: tuple):
    return decoder_adapter.program_tree(key, sizes, _groups())


def program_params(seed: int, cfg: dict, expected=None):
    return weights.fit(_tree(weights.base_key(seed), weights.sizes_of(cfg)),
                       expected)


def _windows(cfg: dict) -> list:
    w = int(cfg.get("sliding_window") or 0)
    return [w if kind.startswith("swa") else 0 for kind in PATTERN]


def prefill_flops(cfg: dict, p: int) -> float:
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return sum(2.0 * layer_matmul_params(cfg) * p
               + 4.0 * h * hd * causal_pairs(p, w) for w in _windows(cfg)) \
        + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_flops(cfg: dict, position: int) -> float:
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return sum(2.0 * layer_matmul_params(cfg)
               + 4.0 * h * hd * (min(position + 1, w) if w else position + 1)
               for w in _windows(cfg)) \
        + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def kernels(cfg: dict) -> dict:
    return {"prefill": ["swa_prefill"], "decode": ["decode_attention"]}


def attention(cfg: dict) -> tuple:
    full = sum(1 for kind in PATTERN if kind.startswith("attn"))
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"],
            {"swa_prefill": len(PATTERN), "decode_attention": full},
            int(cfg.get("sliding_window") or 0))
