"""Pallas kernel shape/dtype sweeps against pure-jnp oracles
(interpret=True on the CPU backend; the TPU compile of the same kernels
is checked in test_tpu_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,kv,g,d,s,block_s", [
    (1, 1, 1, 64, 128, 64),
    (2, 3, 4, 64, 256, 64),
    (2, 2, 2, 128, 512, 256),
    (4, 1, 8, 64, 128, 128),     # MQA-style
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, kv, g, d, s, block_s, dtype, rng):
    q = jnp.asarray(rng.standard_normal((b, kv, g, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), dtype)
    lens = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
    out = decode_attention(q, k, v, lens, block_s=block_s)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_decode_attention_length_masking(rng):
    """Tokens beyond the valid length must not influence the output."""
    b, kv, g, d, s = 2, 2, 2, 64, 128
    q = jnp.asarray(rng.standard_normal((b, kv, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    lens = jnp.asarray([40, 80], jnp.int32)
    out1 = decode_attention(q, k, v, lens, block_s=64)
    k2 = k.at[:, :, 100:].set(999.0)
    v2 = v.at[:, :, 100:].set(-999.0)
    out2 = decode_attention(q, k2, v2, lens, block_s=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("b,t,h,d,block_t", [
    (1, 16, 1, 16, 8),
    (2, 64, 3, 32, 16),
    (2, 32, 2, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_sweep(b, t, h, d, block_t, dtype, rng):
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5, dtype)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.8, 0.999, (b, t, h, d)), dtype)
    u = jnp.asarray(rng.standard_normal((h, d)) * 0.5, jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, h, d, d)) * 0.1, jnp.float32)
    y1, sf1 = rwkv6_scan(r, k, v, w, u, s0, block_t=block_t)
    y2, sf2 = rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(sf1), np.asarray(sf2),
                               **tol(dtype))


def test_rwkv6_state_continuation(rng):
    """Scanning [0:T] equals scanning [0:T/2] then [T/2:T] with the carried
    state (the prefill->decode handoff property)."""
    b, t, h, d = 1, 32, 2, 16
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                             jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.9, 0.999, (b, t, h, d)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, d)), jnp.float32)
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    y_all, s_all = rwkv6_scan(r, k, v, w, u, s0, block_t=8)
    half = t // 2
    y1, s1 = rwkv6_scan(r[:, :half], k[:, :half], v[:, :half], w[:, :half],
                        u, s0, block_t=8)
    y2, s2 = rwkv6_scan(r[:, half:], k[:, half:], v[:, half:], w[:, half:],
                        u, s1, block_t=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_all), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all), atol=1e-5)


@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (1, 16, 1, 16, 8, 8),
    (2, 64, 3, 32, 16, 16),
    (2, 128, 2, 64, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, t, h, p, n, chunk, dtype, rng):
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((b, t, h)),
                                     jnp.float32))
    alog = jnp.asarray(rng.standard_normal((h,)) * 0.3, jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, t, n)), dtype)
    cm = jnp.asarray(rng.standard_normal((b, t, n)), dtype)
    h0 = jnp.asarray(rng.standard_normal((b, h, p, n)) * 0.1, jnp.float32)
    y1, h1 = ssd_scan(x, dt, alog, bm, cm, h0, chunk=chunk)
    y2, h2 = ssd_scan_ref(x, dt, alog, bm, cm, h0)
    t_ = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **t_)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), **t_)


def test_ssd_chunk_invariance(rng):
    b, t, h, p, n = 1, 48, 2, 16, 8
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((b, t, h)),
                                     jnp.float32))
    alog = jnp.asarray(rng.standard_normal((h,)) * 0.3, jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32)
    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    y1, hf1 = ssd_scan(x, dt, alog, bm, cm, h0, chunk=16)
    y2, hf2 = ssd_scan(x, dt, alog, bm, cm, h0, chunk=48)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf1), np.asarray(hf2), atol=1e-4)


def test_pallas_decode_integrated_in_model():
    """Model decode with use_pallas_decode=True (interpret mode on CPU)
    matches the pure-jnp decode path bit-for-bit within tolerance."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("smollm-135m", reduced=True)
    m0 = build_model(cfg)
    m1 = build_model(dataclasses.replace(cfg, use_pallas_decode=True))
    params = m0.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    _, cache = jax.jit(lambda p, b: m0.prefill(p, b, cache_len=16))(
        params, {"tokens": toks[:, :-1]})
    d0, _ = jax.jit(lambda p, c, t: m0.decode_step(p, c, t))(
        params, cache, toks[:, -1:])
    d1, _ = jax.jit(lambda p, c, t: m1.decode_step(p, c, t))(
        params, cache, toks[:, -1:])
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-4)


def test_wkv6_chunked_matches_scan(rng):
    """Beyond-paper chunked-parallel WKV6 == per-step scan (incl. carried
    state and non-multiple sequence lengths)."""
    from repro.models.rwkv6 import wkv6_chunked, wkv6_scan
    b, t, h, d = 2, 77, 3, 16
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                             jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.7, 0.999, (b, t, h, d)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, d)) * 0.5, jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, h, d, d)) * 0.1, jnp.float32)
    y1, s1 = wkv6_scan(r, k, v, w, u, s0)
    for chunk in (16, 32):
        y2, s2 = wkv6_chunked(r, k, v, w, u, s0, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


@pytest.mark.parametrize("b,s,h,kv,d,w,blk", [
    (2, 256, 4, 2, 32, 64, 64),
    (1, 512, 2, 2, 64, 128, 128),
    (2, 128, 3, 1, 16, 1000, 64),    # window >= seq: full causal
    (1, 256, 2, 2, 32, 32, 64),      # window < block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_prefill_sweep(b, s, h, kv, d, w, blk, dtype, rng):
    from repro.kernels.swa_prefill.ops import swa_prefill_attention
    from repro.kernels.swa_prefill.ref import swa_prefill_ref
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    out = swa_prefill_attention(q, k, v, window=w, block=blk)
    kr = jnp.repeat(k, h // kv, 2)
    vr = jnp.repeat(v, h // kv, 2)
    ref = swa_prefill_ref(qr := q, kr, vr, window=w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


# --------------------------------------------------------------------------
# ragged / odd-shape parity (ISSUE 4 satellite): the sweeps above cover
# round power-of-two shapes only; serving hands the kernels ragged ones.
# swa_prefill requires s % block == 0 after clamping (block = min(block,
# s)), so odd lengths run either as a single odd-sized block or with a
# block that divides a non-power-of-two s.
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,kv,d,w,blk", [
    (1, 77, 2, 2, 32, 32, 256),      # odd s, single odd block
    (1, 77, 2, 1, 32, 1000, 256),    # odd s, window >= s (full causal)
    (2, 96, 3, 3, 16, 40, 32),       # non-pow2 s, multi-block, ragged w
    (1, 160, 4, 2, 32, 33, 32),      # batch=1, window straddles blocks
    (1, 64, 2, 2, 32, 1, 32),        # window=1: pure self-attention
    (2, 33, 1, 1, 16, 17, 64),       # prime-ish s, single head
])
def test_swa_prefill_ragged_and_window_edges(b, s, h, kv, d, w, blk, rng):
    from repro.kernels.swa_prefill.ops import swa_prefill_attention
    from repro.kernels.swa_prefill.ref import swa_prefill_ref
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    out = swa_prefill_attention(q, k, v, window=w, block=blk)
    kr = jnp.repeat(k, h // kv, 2)
    vr = jnp.repeat(v, h // kv, 2)
    ref = swa_prefill_ref(q, kr, vr, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_swa_prefill_window_one_is_self_attention(rng):
    """window=1 must reduce to attending the own position only (softmax
    over one logit == V at that position)."""
    from repro.kernels.swa_prefill.ops import swa_prefill_attention
    b, s, h, d = 1, 96, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    out = swa_prefill_attention(q, k, v, window=1, block=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v), atol=1e-5)


@pytest.mark.parametrize("b,kv,g,d,s,block_s,lens", [
    (1, 1, 1, 32, 77, 512, [1]),          # batch=1, odd s, minimal cache
    (1, 2, 4, 32, 77, 512, [77]),         # odd s, full-length cache
    (2, 2, 2, 32, 96, 32, [31, 33]),      # lens straddle block edges
    (3, 1, 2, 16, 96, 32, [32, 64, 96]),  # lens exactly on block edges
    (1, 3, 1, 64, 60, 20, [59]),          # non-pow2 everything, g=1
])
def test_decode_attention_ragged_lengths(b, kv, g, d, s, block_s, lens,
                                         rng):
    q = jnp.asarray(rng.standard_normal((b, kv, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    ln = jnp.asarray(lens, jnp.int32)
    out = decode_attention(q, k, v, ln, block_s=block_s)
    ref = decode_attention_ref(q, k, v, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_length_one_reads_first_token(rng):
    """length=1 must return exactly V[:, :, 0] regardless of cache noise."""
    b, kv, g, d, s = 1, 2, 2, 32, 64
    q = jnp.asarray(rng.standard_normal((b, kv, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    out = decode_attention(q, k, v, jnp.asarray([1], jnp.int32),
                           block_s=32)
    expect = np.broadcast_to(np.asarray(v)[:, :, 0][:, :, None, :],
                             (b, kv, g, d))
    np.testing.assert_allclose(np.asarray(out), expect, atol=1e-5)


def test_swa_prefill_matches_model_blocked_attention(rng):
    """The kernel agrees with the model's blocked_attention SWA path."""
    from repro.kernels.swa_prefill.ops import swa_prefill_attention
    from repro.models.attention import blocked_attention
    b, s, h, kv, d, w = 2, 256, 4, 2, 32, 96
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    ref = blocked_attention(q, k, v, pos, pos, causal=True, window=w,
                            scale=d ** -0.5, block_q=64, block_k=64)
    out = swa_prefill_attention(q, k, v, window=w, block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
