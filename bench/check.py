"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the one with the most served tokens, is run through the plain reference
once: each prompt followed by the tokens the program served.  At every
served token the reference's logits give the gap by which the served
token's logit lies below the reference's best.  Two numbers come of the
sample: the widest gap, which is compared, and the mean gap over every
served token, which is only reported.  Served tokens are greedy, so a
sound program reads only rounding: a token it chose where the reference's
top two nearly tie.

The control reads the same gaps for the token that the float8 (e4m3
W8A8) reference puts first at each of the same positions.
"""
from __future__ import annotations

import importlib

import numpy as np

from bench import gen


def sample(rows: list, lengths: list, seed: int, min_tokens: int,
           max_requests: int) -> list:
    """Rows to compare: the longest, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not rows:
        return []
    order = list(gen.rng_for(seed, 4).permutation(len(rows)))
    longest = int(np.argmax(lengths))
    order.remove(longest)
    picked, total = [longest], lengths[longest]
    for j in order:
        if total >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(int(j))
        total += lengths[j]
    return [rows[j] for j in picked]


def _reference(cfg: dict):
    return importlib.import_module(f"bench.configs.{cfg['reference']}")


def _rows_per_block(cfg: dict, t: int) -> int:
    """Rows per block so one block's attention scores stay near 1 GB."""
    per_row = cfg["num_attention_heads"] * t * t * 4
    return int(max(1, min(8, 1e9 // per_row)))


def compare(cfg: dict, seed: int, prompts: list, served: list,
            decode_hi: int, control: bool = False) -> dict:
    """Widest gap of the served tokens (and of the control's choices).

    ``prompts``: token ids per request; ``served``: the program's tokens
    per request (first token included).  Sequences are padded to one
    length, prompt + ``decode_hi``, so every run compiles one shape."""
    import jax.numpy as jnp
    ref = _reference(cfg)
    if not prompts:
        return {"logit_gap": float("inf"), "logit_gap_mean": float("inf"),
                "control_logit_gap": float("inf"),
                "control_logit_gap_mean": float("inf"), "tokens": 0,
                "requests": 0}
    p = len(prompts[0])
    t = p + decode_hi
    rows = _rows_per_block(cfg, t)
    n = len(prompts)
    n_pad = -(-n // rows) * rows
    k = decode_hi + 1
    tokens = np.zeros((n_pad, t), np.int32)
    pos = np.full((n_pad, k), p - 1, np.int32)
    want = np.zeros((n_pad, k), np.int32)
    valid = np.zeros((n_pad, k), bool)
    for i in range(n_pad):
        src = min(i, n - 1)
        s = np.asarray(served[src], np.int32)
        seq = np.concatenate([np.asarray(prompts[src], np.int32), s[:-1]])
        tokens[i, :seq.size] = seq
        m = s.size
        pos[i, :m] = np.arange(p - 1, p - 1 + m)
        want[i, :m] = s
        valid[i, :m] = i < n
    hid = ref.final_hidden(cfg, seed, tokens, rows=rows)
    logits = ref.position_logits(cfg, seed, hid, pos, rows=rows)
    del hid
    chid = ref.final_hidden(cfg, seed, tokens, quant="fp8", rows=rows) \
        if control else None
    clog = ref.position_logits(cfg, seed, chid, pos, quant="fp8",
                               rows=rows) if control else None
    gaps, cgaps = [], []
    for bi, lg in enumerate(logits):
        sl = slice(bi * rows, (bi + 1) * rows)
        top = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, jnp.asarray(want[sl])[..., None],
                                  -1)[..., 0]
        gaps.append(np.asarray(top - got))
        if control:
            pick = jnp.argmax(clog[bi], -1)
            cg = top - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
            cgaps.append(np.asarray(cg))
    gap = np.concatenate(gaps)[valid]
    out = {"logit_gap": float(gap.max()), "logit_gap_mean": float(gap.mean()),
           "flips": int((gap > 0).sum()), "tokens": int(valid.sum()),
           "requests": n}
    if control:
        cg = np.concatenate(cgaps)[valid]
        out.update(control_logit_gap=float(cg.max()),
                   control_logit_gap_mean=float(cg.mean()),
                   control_flips=int((cg > 0).sum()))
    return out
