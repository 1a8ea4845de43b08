"""Multi-device integration tests (subprocess with 8 fake CPU devices).

The main pytest process keeps 1 device; these tests spawn a fresh python
with XLA_FLAGS=--xla_force_host_platform_device_count=8 and check that the
sharded program (a) compiles+runs and (b) matches the single-device result —
the strongest SPMD-correctness property available without hardware.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.models.api import init_params
from repro.models.sharding import param_specs, batch_specs, shardings
from repro.data import make_batch
from repro.train.loop import make_train_step, init_state
from repro.train.optimizer import OptConfig

arch = %(arch)r
cfg = get_config(arch, reduced=True)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

B, S = 4, 16
batch = make_batch(cfg, B, S + (cfg.num_patch_tokens or 0), 0)
oc = OptConfig(lr=1e-3)

# single-device reference
m1 = build_model(cfg)
s1 = init_state(m1, jax.random.key(0), oc).as_dict()
_, met1 = jax.jit(make_train_step(m1, oc))(s1, batch)

# sharded
with mesh:
    m2 = build_model(cfg, mesh=mesh)
    s2 = init_state(m2, jax.random.key(0), oc).as_dict()
    pspecs = param_specs(jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)), mesh)
    sspecs = {"params": pspecs, "opt": {"mu": pspecs, "nu": pspecs,
              "step": jax.sharding.PartitionSpec()}}
    sshard = shardings(sspecs, mesh)
    s2 = jax.device_put(s2, sshard)
    bshard = shardings(batch_specs(batch, mesh), mesh)
    batch2 = jax.device_put(batch, bshard)
    step = jax.jit(make_train_step(m2, oc), in_shardings=(sshard, bshard))
    _, met2 = step(s2, batch2)

print(json.dumps({"loss1": float(met1["loss"]), "loss2": float(met2["loss"]),
                  "g1": float(met1["grad_norm"]), "g2": float(met2["grad_norm"])}))
"""


def run_sharded(arch: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT % {"arch": arch}],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v3-671b",
                                  "zamba2-2.7b", "rwkv6-1.6b"])
def test_sharded_train_step_matches_single_device(arch):
    r = run_sharded(arch)
    assert abs(r["loss1"] - r["loss2"]) < 0.05, r
    assert abs(r["g1"] - r["g2"]) / max(r["g1"], 1e-6) < 0.15, r
