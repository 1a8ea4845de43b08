"""Seeded random weights of a decoder configuration, defined leaf by leaf.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed key, leaf),
layer)``, so the same numbers come out whether a leaf is made for one
layer or for all layers at once under ``vmap``.  The program's copy is
made on the device in one jitted call; the plain reference makes each
layer again from the seed when it needs it, and so takes nothing that the
program made.

Scales follow common initialisation: matrices ~ N(0, 1/fan_in), the
embedding ~ N(0, 0.02^2), and every RMSNorm gain is ``1 + s`` with
s ~ N(0, NORM_STD^2), so the gains are exercised too.  All leaves are
rounded to bfloat16, the type they are served in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1
EMBED_STD = 0.02
GLOBAL_LEAVES = ("embed", "final_norm", "head")
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
_LEAF_ID = {n: i for i, n in enumerate(GLOBAL_LEAVES + LAYER_LEAVES)}


def sizes_of(cfg: dict) -> tuple:
    """The hashable size tuple of a configuration file's published keys."""
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            bool(cfg["tie_word_embeddings"]))


def base_key(seed: int):
    """A key from any whole number (negative or wider than 32 bits)."""
    s = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF),
                              np.uint32(s >> 32))


def _shape_std(sizes: tuple, name: str):
    _, d, h, kv, hd, ff, v, _ = sizes
    return {
        "embed": ((v, d), EMBED_STD),
        "final_norm": ((d,), NORM_STD),
        "head": ((d, v), d ** -0.5),
        "attn_norm": ((d,), NORM_STD),
        "wq": ((d, h * hd), d ** -0.5),
        "wk": ((d, kv * hd), d ** -0.5),
        "wv": ((d, kv * hd), d ** -0.5),
        "wo": ((h * hd, d), (h * hd) ** -0.5),
        "mlp_norm": ((d,), NORM_STD),
        "w_gate": ((d, ff), d ** -0.5),
        "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
    }[name]


def _leaf(key, sizes, name, layer=None):
    k = jax.random.fold_in(key, _LEAF_ID[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    shape, std = _shape_std(sizes, name)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def global_leaves(key, sizes: tuple) -> dict:
    names = GLOBAL_LEAVES if not sizes[7] else ("embed", "final_norm")
    return {n: _leaf(key, sizes, n) for n in names}


def layer_leaves(key, sizes: tuple, layer) -> dict:
    return {n: _leaf(key, sizes, n, layer) for n in LAYER_LEAVES}


@partial(jax.jit, static_argnums=(1,))
def _program_tree(key, sizes: tuple):
    g = global_leaves(key, sizes)
    layers = jax.vmap(lambda i: layer_leaves(key, sizes, i))(
        jnp.arange(sizes[0]))
    tree = {"embed": g["embed"], "final_norm": g["final_norm"],
            "groups": ({"norm1": layers["attn_norm"],
                        "attn": {k: layers[k] for k in ("wq", "wk", "wv",
                                                        "wo")},
                        "norm2": layers["mlp_norm"],
                        "mlp": {k: layers[k] for k in ("w_up", "w_down",
                                                       "w_gate")}},)}
    if not sizes[7]:
        tree["head"] = g["head"]
    return tree


def program_params(seed: int, cfg: dict, expected=None):
    """The program's parameter tree, made on the device in one call.

    ``expected`` (``jax.eval_shape`` of the program's own ``init``) is
    checked leaf for leaf: structure, shapes and dtypes."""
    tree = _program_tree(base_key(seed), sizes_of(cfg))
    if expected is not None:
        got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), expected)
        if jax.tree.structure(got) != jax.tree.structure(want) or \
                jax.tree.leaves(got) != jax.tree.leaves(want):
            raise ValueError(f"weights do not fit the program's tree:\n"
                             f"{got}\nvs\n{want}")
    return tree
