"""The plain reference agrees with the program's float32 forward at a
small size, on the same seeded weights: it is the model the program
claims to compute."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.configs import decoder_reference as ref
from bench.tests.conftest import tiny_cell


@pytest.mark.parametrize("window", [None, 5])
def test_reference_matches_program_f32(window):
    from repro.models import build_model

    from bench import harness
    cfg = dict(tiny_cell().cfg, sliding_window=window)
    pc = dataclasses.replace(harness.program_config(cfg), dtype="float32",
                             param_dtype="float32", use_pallas_prefill=False,
                             use_pallas_decode=False)
    model = build_model(pc)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.program_params(7, cfg))
    tokens = np.asarray(jax.random.randint(jax.random.key(1), (3, 12), 0,
                                           cfg["vocab_size"]), np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    hid = ref.final_hidden(cfg, 7, tokens, rows=3)
    pos = np.tile(np.arange(12, dtype=np.int32), (3, 1))
    got = ref.position_logits(cfg, 7, hid, pos, rows=3)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
