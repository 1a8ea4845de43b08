"""Compile the serving path's Pallas kernels, and one full decode step, for a
described TPU v5e at smollm-135m widths.

No chip is attached: the TPU compiler runs here against a described
``v5e:2x2`` topology and raises what the chip's compiler would raise (block
shapes that break the (8, 128) tiling rule, VMEM overruns), which the
interpret-mode tests in test_kernels.py cannot see.  The topology is
described inside a fixture, never at import, so that every xdist worker
collects the same tests and only the worker running this file loads the
TPU library.  All compiles run in this process with the persistent
compilation cache off (a TPU entry written here could not be read back
without a chip).
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# smollm-135m attention widths (configs/smollm_135m.py)
KV, G, D = 3, 3, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_decode_attention_compiles_for_tpu(one_chip):
    from repro.kernels.decode_attention.decode_attention import \
        decode_attention_pallas
    b, s = 4, 1024
    fn = jax.jit(lambda q, k, v, lens: decode_attention_pallas(
        q, k, v, lens, block_s=512, interpret=False))
    compiled = fn.lower(_sds((b, KV, G, D), jnp.bfloat16, one_chip),
                        _sds((b, KV, s, D), jnp.bfloat16, one_chip),
                        _sds((b, KV, s, D), jnp.bfloat16, one_chip),
                        _sds((b,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_swa_prefill_compiles_for_tpu(one_chip):
    from repro.kernels.swa_prefill.swa_prefill import swa_prefill_pallas
    b, s = 4, 128
    fn = jax.jit(lambda q, k, v: swa_prefill_pallas(
        q, k, v, window=s, block_q=s, block_k=s, interpret=False))
    compiled = fn.lower(_sds((b, KV * G, s, D), jnp.bfloat16, one_chip),
                        _sds((b, KV, s, D), jnp.bfloat16, one_chip),
                        _sds((b, KV, s, D), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_decode_step_compiles_for_tpu(one_chip, monkeypatch):
    """A whole 30-layer smollm-135m decode step at the serving path's cache
    length (128-token prompt + 32 decode + 1) reaches the Pallas kernel."""
    from repro.configs import get_config
    from repro.kernels.decode_attention import ops
    from repro.models import build_model
    # the described chip is not the default backend, so steer the kernel
    # wrapper onto its compiled branch here, and drop traces cached with the
    # interpreter branch
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    try:
        cfg = dataclasses.replace(get_config("smollm-135m"),
                                  use_pallas_decode=True)
        model = build_model(cfg)
        b, cache_len = 4, 128 + 32 + 1

        def on_chip(tree):
            return jax.tree.map(
                lambda x: _sds(x.shape, x.dtype, one_chip), tree)

        params = on_chip(jax.eval_shape(model.init, jax.random.key(0)))
        cache = on_chip(jax.eval_shape(
            lambda: model.init_cache(b, cache_len)))
        tok = _sds((b, 1), jnp.int32, one_chip)
        compiled = jax.jit(model.decode_step).lower(
            params, cache, tok).compile()
        assert "tpu_custom_call" in compiled.as_text()
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("kernel", ["decode_attention", "swa_prefill"])
def test_kernels_carry_their_names_for_tpu(one_chip, kernel):
    """Each Pallas kernel lowers under its own name, so a device trace
    tells the two apart without knowing which program ran them."""
    from repro.kernels.decode_attention.decode_attention import \
        decode_attention_pallas
    from repro.kernels.swa_prefill.swa_prefill import swa_prefill_pallas
    b, s = 2, 512
    bf = jnp.bfloat16
    if kernel == "decode_attention":
        fn = jax.jit(lambda q, k, v, lens: decode_attention_pallas(
            q, k, v, lens, interpret=False))
        args = (_sds((b, KV, G, D), bf, one_chip),
                _sds((b, KV, s, D), bf, one_chip),
                _sds((b, KV, s, D), bf, one_chip),
                _sds((b,), jnp.int32, one_chip))
    else:
        fn = jax.jit(lambda q, k, v: swa_prefill_pallas(
            q, k, v, window=s, interpret=False))
        args = (_sds((b, KV * G, s, D), bf, one_chip),
                _sds((b, KV, s, D), bf, one_chip),
                _sds((b, KV, s, D), bf, one_chip))
    text = fn.lower(*args).as_text()
    assert f'kernel_name = "{kernel}"' in text
