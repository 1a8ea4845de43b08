"""Plain reference of a Llama-style decoder, as the configurations state it.

Both configurations here (SmolLM-135M and H2O-Danube-1.8B) are decoders of
this shape: token embedding; per layer RMSNorm -> grouped-query attention
with rotary positions (rotate-half form, ``rope_theta``), causal and, where
``sliding_window`` is set, windowed -> residual -> RMSNorm -> SwiGLU MLP
-> residual; final RMSNorm and the head (the embedding's transpose where
``tie_word_embeddings``).  No KV cache, no kernels, no batching tricks: the
whole sequence goes through each layer at once, in float32 at the
``highest`` matmul precision, over the bfloat16 weights that
``bench.weights`` draws from the seed.  It imports nothing of the program.

The forward runs layer by layer and in blocks of rows, making each layer's
weights again from the seed, so it fits beside nothing on the chip.

``quant="fp8"`` is the control, a step below the configuration's
bfloat16: every linear layer's weights are rounded to float8 e4m3 per
output channel and its input activations per token (W8A8), each scaled so
its largest magnitude is 448; the embedding rows are rounded the same way.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W


def _rms(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


F8_MAX = 448.0          # largest float8 e4m3 (fn) number
F8_MIN_EXP = -6         # its smallest normal exponent
F8_MANT = 3             # its stored mantissa bits


def f8_round(x):
    """Round float32 to the nearest float8 e4m3 number (ties to even),
    in float32 arithmetic, so it runs alike on every backend."""
    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** F8_MIN_EXP)))
    # log2 can land a hair off at exact powers of two: correct it
    e = jnp.where(2.0 ** e > a, e - 1, e)
    e = jnp.where(2.0 ** (e + 1) <= a, e + 1, e)
    e = jnp.maximum(e, F8_MIN_EXP)
    step = 2.0 ** (e - F8_MANT)
    return jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, F8_MAX)


def _quantize(x, axis):
    """Round ``x`` to float8 e4m3 with one scale along ``axis`` (the
    reduced dimension)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return f8_round(x / scale) * scale


def _lin(x, w, quant):
    if quant == "fp8":
        x = _quantize(x, -1)              # per token
        w = _quantize(w, 0)               # per output channel
    return x @ w


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]        # (T, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


@partial(jax.jit, static_argnames=("sizes", "theta", "eps", "window",
                                   "quant"))
def _layer(w, x, *, sizes, theta, eps, window, quant):
    _, d, h, kv, hd, _, _, _ = sizes
    r, t, _ = x.shape
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(t)
    a = _rms(x, w["attn_norm"], eps)
    q = _rope(_lin(a, w["wq"], quant).reshape(r, t, h, hd), pos, theta)
    k = _rope(_lin(a, w["wk"], quant).reshape(r, t, kv, hd), pos, theta)
    v = _lin(a, w["wv"], quant).reshape(r, t, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    rel = pos[:, None] - pos[None, :]
    mask = rel >= 0
    if window:
        mask = mask & (rel < window)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + _lin(o.reshape(r, t, h * hd), w["wo"], quant)
    m = _rms(x, w["mlp_norm"], eps)
    g = _lin(m, w["w_gate"], quant)
    u = _lin(m, w["w_up"], quant)
    return x + _lin(jax.nn.silu(g) * u, w["w_down"], quant)


@partial(jax.jit, static_argnames=("sizes",))
def _layer_weights(key, layer, *, sizes):
    return W.layer_leaves(key, sizes, layer)


@partial(jax.jit, static_argnames=("sizes",))
def _global_weights(key, *, sizes):
    return W.global_leaves(key, sizes)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _embed(g, tokens, *, eps, quant):
    e = g["embed"].astype(jnp.float32)
    if quant == "fp8":
        e = _quantize(e, 1)
    return e[tokens]


@partial(jax.jit, static_argnames=("eps", "quant", "tied"))
def _logits(g, x, *, eps, quant, tied):
    x = _rms(x, g["final_norm"].astype(jnp.float32), eps)
    head = (g["embed"].astype(jnp.float32).T if tied
            else g["head"].astype(jnp.float32))
    return _lin(x, head, quant)


def final_hidden(cfg: dict, seed: int, tokens: np.ndarray, quant=None,
                 rows: int = 8):
    """Hidden states after the last layer, (R, T, d) float32 on the device.

    ``tokens``: (R, T) int32.  Runs layer by layer, ``rows`` rows at a
    time."""
    sizes = W.sizes_of(cfg)
    key = W.base_key(seed)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    window = int(cfg.get("sliding_window") or 0)
    with jax.default_matmul_precision("highest"):
        g = _global_weights(key, sizes=sizes)
        blocks = [_embed(g, jnp.asarray(tokens[i:i + rows]), eps=eps,
                         quant=quant)
                  for i in range(0, len(tokens), rows)]
        del g
        for layer in range(sizes[0]):
            w = _layer_weights(key, jnp.int32(layer), sizes=sizes)
            blocks = [_layer(w, x, sizes=sizes, theta=theta, eps=eps,
                             window=window, quant=quant) for x in blocks]
            del w
    return blocks


def position_logits(cfg: dict, seed: int, blocks, positions: np.ndarray,
                    quant=None, rows: int = 8):
    """Logits at ``positions`` (R, K) of each row: (R, K, vocab) float32,
    kept on the device block by block."""
    sizes = W.sizes_of(cfg)
    key = W.base_key(seed)
    eps = float(cfg["rms_norm_eps"])
    out = []
    with jax.default_matmul_precision("highest"):
        g = _global_weights(key, sizes=sizes)
        for bi, x in enumerate(blocks):
            p = jnp.asarray(positions[bi * rows:(bi + 1) * rows])
            h = jnp.take_along_axis(x, p[..., None], axis=1)
            out.append(_logits(g, h, eps=eps, quant=quant, tied=sizes[7]))
    return out
