"""Adapter of a dense Llama-style decoder (SmolLM-135M, H2O-Danube-1.8B).

A configuration file names its adapter (``"adapter"``), a module of this
directory that gives the harness, the weights and the metrics everything
that depends on the architecture:

* ``program_config(cfg)``: the program's ``ModelConfig`` at the file's sizes;
* ``program_params(seed, cfg, expected=None)``: the program's parameter
  tree, drawn from the seed on the device in one call;
* ``prefill_flops(cfg, p)``, ``decode_flops(cfg, position)``: model FLOPs
  of a prompt and of one decoded token (``step.mfu``);
* ``attention(cfg)``: ``(heads, kv_heads, head_dim, kernel_layers,
  window)``, where ``kernel_layers`` maps each Pallas kernel's name to the
  number of layers that run it (the kernels' rooflines);
* ``kernels(cfg)``: per program (``prefill``, ``decode``) the names of the
  Pallas kernels its entries must hold (the kernel guard).

Here every layer is ``program.block`` (``attn+mlp`` or ``swa+mlp``) and
runs the configured kernels.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from bench import weights
from bench.counts.model import decode_flops, prefill_flops  # noqa: F401

KERNELS = {"prefill": "swa_prefill", "decode": "decode_attention"}


def program_config(cfg: dict):
    """The program's ModelConfig at the configuration file's sizes, with
    both Pallas kernel routes on."""
    from repro.configs import get_config
    prog = cfg["program"]
    layers = cfg["num_hidden_layers"]
    return dataclasses.replace(
        get_config(prog["arch"]), num_layers=layers,
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        blocks=(prog["block"],) * layers,
        window_size=int(cfg.get("sliding_window") or 0),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"], use_pallas_prefill=True,
        use_pallas_decode=True)


def block_group(key, sizes: tuple, first: int, n: int) -> dict:
    """The program's group of ``n`` attention + MLP layers from layer
    ``first`` on, each leaf stacked over the layers."""
    layers = jax.vmap(lambda i: weights.layer_leaves(key, sizes, i))(
        jnp.arange(first, first + n))
    return {"norm1": layers["attn_norm"],
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": layers["mlp_norm"],
            "mlp": {k: layers[k] for k in ("w_up", "w_down", "w_gate")}}


def program_tree(key, sizes: tuple, groups: tuple) -> dict:
    """The program's whole tree; ``groups`` holds each group's
    ``(first layer, layers)``."""
    g = weights.global_leaves(key, sizes)
    tree = {"embed": g["embed"], "final_norm": g["final_norm"],
            "groups": tuple(block_group(key, sizes, a, n)
                            for a, n in groups)}
    if not sizes[7]:
        tree["head"] = g["head"]
    return tree


@partial(jax.jit, static_argnums=(1,))
def _program_tree(key, sizes: tuple):
    return program_tree(key, sizes, ((0, sizes[0]),))


def program_params(seed: int, cfg: dict, expected=None):
    """The program's parameter tree, made on the device in one call.

    ``expected`` (``jax.eval_shape`` of the program's own ``init``) is
    checked leaf for leaf: structure, shapes and dtypes."""
    return weights.fit(_program_tree(weights.base_key(seed),
                                     weights.sizes_of(cfg)), expected)


def kernels(cfg: dict) -> dict:
    """The kernel each program's entries must hold, where the
    configuration's ``program.kernels`` turns that program's guard on."""
    on = cfg["program"]["kernels"]
    return {prog: [name] if prog in on else []
            for prog, name in KERNELS.items()}


def attention(cfg: dict) -> tuple:
    """Every layer runs each guarded kernel."""
    layers = cfg["num_hidden_layers"]
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"],
            {k: layers for names in kernels(cfg).values() for k in names},
            int(cfg.get("sliding_window") or 0))
