"""MoE dispatch-path equivalence tests (single-device + subprocess SPMD)."""
import json
import os
import subprocess
import sys

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.moe import (capacity_for, init_moe, moe_fwd, route_topk)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _tiny_cfg(**kw):
    cfg = get_config("kimi-k2-1t-a32b", reduced=True)
    return dataclasses.replace(cfg, num_experts=8, num_experts_per_tok=2,
                               d_model=64, moe_d_ff=32,
                               moe_capacity_factor=8.0, **kw)


def test_capacity_floor_and_cap():
    assert capacity_for(8, 8, 256, 1.25) >= 8       # decode: zero-drop floor
    assert capacity_for(1, 2, 4, 1.25) <= 2          # never exceeds t*k
    c = capacity_for(65536, 8, 256, 1.25)
    assert c >= 65536 * 8 * 1.25 / 256
    assert c % 4 == 0


def test_route_topk_softmax_vs_sigmoid():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((16, 8)),
                         jnp.float32)
    bias = jnp.zeros((8,))
    for kind in ("softmax", "sigmoid"):
        w, ids, probs = route_topk(logits, bias, 2, kind)
        assert w.shape == (16, 2) and ids.shape == (16, 2)
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
        assert int(ids.max()) < 8


def test_sigmoid_bias_changes_selection_not_weights():
    """DeepSeek-V3 aux-free balancing: the bias shifts WHICH experts are
    picked but the combine weights come from unbiased scores."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    b0 = jnp.zeros((8,))
    b1 = b0.at[3].set(10.0)  # strongly favor expert 3
    _, ids0, _ = route_topk(logits, b0, 2, "sigmoid")
    w1, ids1, _ = route_topk(logits, b1, 2, "sigmoid")
    assert (ids1 == 3).any(axis=1).all(), "bias must pull expert 3 in"
    # weights still normalized from sigmoid scores
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 1.0, atol=1e-5)


def test_moe_fwd_no_drop_equals_dense_sum():
    """With no-drop capacity, the MoE output equals the explicit per-token
    weighted sum of expert FFNs."""
    cfg = _tiny_cfg()
    params = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 6, 64)) * 0.5
    y, aux = jax.jit(lambda p, x: moe_fwd(p, x, cfg))(params, x)

    xt = x.reshape(-1, 64)
    logits = xt @ params["router"]
    w, ids, _ = route_topk(logits, params["router_bias"],
                           cfg.num_experts_per_tok, cfg.moe_router_kind)
    ref = jnp.zeros_like(xt)
    for e in range(cfg.num_experts):
        h = xt @ params["wg"][e]
        u = xt @ params["wu"][e]
        fe = (jax.nn.silu(h) * u) @ params["wd"][e]
        we = jnp.where(ids == e, w, 0.0).sum(-1)
        ref = ref + fe * we[:, None]
    from repro.models.mlp import mlp_fwd
    if "shared" in params:
        ref = ref + mlp_fwd(params["shared"], xt, "swiglu")
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 64)),
                               np.asarray(ref), atol=1e-4, rtol=1e-4)


PARTIAL_EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.moe import init_moe, moe_fwd, moe_fwd_ep

cfg = get_config("kimi-k2-1t-a32b", reduced=True)
cfg = dataclasses.replace(cfg, num_experts=8, num_experts_per_tok=2,
                          d_model=64, moe_d_ff=32, moe_capacity_factor=8.0)
params = init_moe(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (8, 4, 64)) * 0.5
y_ref, _ = jax.jit(lambda p, x: moe_fwd(p, x, cfg))(params, x)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg2 = dataclasses.replace(cfg, moe_partial_ep=True)
with mesh:
    y_ep, _ = jax.jit(lambda p, x: moe_fwd_ep(
        p, x, cfg2, mesh, ("data",), "model"))(params, x)
    y_g, _ = jax.jit(lambda p, x: moe_fwd_ep(
        p, x, cfg, mesh, ("data",), "model"))(params, x)
print(json.dumps({"partial": float(jnp.abs(y_ep - y_ref).max()),
                  "gather": float(jnp.abs(y_g - y_ref).max())}))
"""


@pytest.mark.slow
def test_expert_parallel_paths_match_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", PARTIAL_EP_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["partial"] < 1e-4, r
    assert r["gather"] < 1e-4, r
