"""The program's weights, as the decoder's adapter makes them (one jitted
call, all layers under vmap), equal the leaves the reference makes again
layer by layer, and fit the program's own parameter tree."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import weights
from bench.configs import decoder_adapter
from bench.tests.conftest import tiny_cell


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 3, 2 ** 40 + 1, -7])
def test_program_tree_equals_per_layer_leaves(seed):
    from repro.models import build_model

    cfg = tiny_cell().cfg
    model = build_model(decoder_adapter.program_config(cfg))
    expected = jax.eval_shape(model.init, jax.random.key(0))
    tree = decoder_adapter.program_params(seed, cfg, expected)
    sizes = weights.sizes_of(cfg)
    key = weights.base_key(seed)
    g = weights.global_leaves(key, sizes)
    assert np.array_equal(tree["head"], g["head"])
    grp = tree["groups"][0]
    for layer in range(sizes[0]):
        lv = weights.layer_leaves(key, sizes, layer)
        assert np.array_equal(grp["attn"]["wq"][layer], lv["wq"])
        assert np.array_equal(grp["mlp"]["w_down"][layer], lv["w_down"])
        assert np.array_equal(grp["norm2"][layer], lv["mlp_norm"])


def test_seeds_give_different_weights():
    cfg = tiny_cell().cfg
    a = decoder_adapter.program_params(1, cfg)
    b = decoder_adapter.program_params(2, cfg)
    assert not np.array_equal(a["embed"], b["embed"])


def test_tree_mismatch_is_refused():
    from repro.models import build_model

    cfg = tiny_cell().cfg
    pc = dataclasses.replace(decoder_adapter.program_config(cfg), d_ff=256)
    expected = jax.eval_shape(build_model(pc).init, jax.random.key(0))
    with pytest.raises(ValueError):
        decoder_adapter.program_params(0, cfg, expected)
