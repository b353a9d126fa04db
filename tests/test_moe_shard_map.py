"""shard_map EP MoE vs its unsharded form, the static-capacity dispatch
(``moe_capacity``): numeric equivalence on a real multi-device mesh
(subprocess: device-count forcing must precede jax init)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.moe import moe_capacity, moe_spec
from repro.models.moe_shard_map import moe_shard_map
from repro.models.modules import init_params
from repro.sharding.ctx import sharding_ctx
from jax.sharding import AxisType

cfg = get_config("deepseek-moe-16b", smoke=True)
# high capacity so neither path drops tokens -> exact equivalence expected
cfg = replace(cfg, capacity_factor=8.0, n_shared_experts=0)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
params = init_params(moe_spec(cfg), jax.random.key(0))
B, S = 4, 16
x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model), jnp.float32)

with mesh, sharding_ctx(mesh, {"batch": ("data",), "expert_buf": "model"}):
    y_dense, aux_d = jax.jit(lambda p, x: moe_capacity(p, x, cfg))(params, x)
    y_ep, aux_e = jax.jit(
        lambda p, x: moe_shard_map(p, x, cfg, mesh=mesh, data_axes=("data",))
    )(params, x)

err = float(jnp.abs(y_dense - y_ep).max())
rel = err / float(jnp.abs(y_dense).max())
print("MAXERR", err, "REL", rel)
print("LB", float(aux_d["lb_loss"]), float(aux_e["lb_loss"]))
print("DROP", float(aux_d["dropped_frac"]), float(aux_e["dropped_frac"]))
assert rel < 2e-5, (err, rel)
assert abs(float(aux_d["lb_loss"]) - float(aux_e["lb_loss"])) < 1e-4
assert float(aux_e["dropped_frac"]) == 0.0
print("OK")
"""


@pytest.mark.slow
def test_shard_map_moe_matches_dense_on_8_devices():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        cwd=REPO,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert r.returncode == 0, r.stdout[-3000:] + "\n" + r.stderr[-3000:]
    assert "OK" in r.stdout
