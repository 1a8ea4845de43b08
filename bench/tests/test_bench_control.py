"""The control: the plain reference in float8 (e4m3 W8A8), the step below
the configurations' bfloat16, put in the program's place on the same
prompts and tokens, comes out not correct under the limit that sound runs
keep.  At this tiny size sound runs read a widest gap of 0.008-0.037 and
the control 0.32-0.74 (CPU, six seeds); the cells' limits are set from
chip readings at their own sizes (PERF.md)."""
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from bench import harness
from bench.configs.decoder_reference import F8_MAX, f8_round
from bench.tests.conftest import tiny_run


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_fp8_control_is_not_correct(seed):
    run, res = tiny_run(seed, control=True)
    r = res["readings"]
    assert harness.correct(res["checks"]), res["checks"]
    assert not harness.correct(res["control_checks"]), res["control_checks"]
    assert r["control_logit_gap"] > 3 * r["logit_gap"], r
    assert r["control_flips"] > r["flips"], r


def test_f8_round_matches_float8_e4m3():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(0, 50, 20000), rng.normal(0, 0.01, 2000),
                        [0.0, 1.0, 1.0625, 1.1875, 2 ** -6, 2 ** -9,
                         3 * 2 ** -10, F8_MAX, -F8_MAX]])
    x = np.clip(x, -F8_MAX, F8_MAX).astype(np.float32)
    want = x.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    assert np.array_equal(np.asarray(f8_round(jnp.asarray(x))), want)
