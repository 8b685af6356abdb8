"""Expert-parallel shard_map MoE must match the plain GSPMD formulation.

Subprocess with 4 host devices: the same params/tokens are run through
``apply_moe`` (a) with no mesh (dense-host path) and (b) under a
(data=2, model=2) mesh where E % model == 0 engages the EP shard_map path.
The dispatch is dropless on both paths, so outputs, the aux loss and the
per-expert routing counts must agree.
"""
import os

import pytest
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.models import moe
from repro.models.schema import init_tree

cfg = get_config("olmoe-1b-7b").smoke()
assert cfg.num_experts % 2 == 0 and cfg.moe_shard == "experts"

schema = moe.moe_schema(cfg)
params = init_tree(schema, jax.random.key(0))
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32).astype(cfg.activation_dtype)

# (a) dense-host path (no ambient mesh).
y_ref, aux_ref, st_ref = jax.jit(lambda p, h: moe.apply_moe(p, h, cfg))(params, x)

# (b) expert-parallel path under the mesh, entered with jax.set_mesh as
# the product code does.
mesh = jax.make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    y_ep, aux_ep, st_ep = jax.jit(lambda p, h: moe.apply_moe(p, h, cfg))(params, x)

np.testing.assert_allclose(
    np.asarray(y_ref, np.float32), np.asarray(y_ep, np.float32),
    rtol=3e-2, atol=3e-2)
np.testing.assert_allclose(float(aux_ref), float(aux_ep), rtol=1e-2, atol=1e-2)
np.testing.assert_array_equal(np.asarray(st_ref["counts"]), np.asarray(st_ep["counts"]))
assert int(st_ref["dropped"]) == int(st_ep["dropped"]) == 0

# (c) batch=1 (long_500k decode regime): EP must fall back to
# model-only manual axes and still agree.
x1 = x[:1, :1]
y1_ref, _, _ = jax.jit(lambda p, h: moe.apply_moe(p, h, cfg))(params, x1)
with jax.set_mesh(mesh):
    y1_ep, _, _ = jax.jit(lambda p, h: moe.apply_moe(p, h, cfg))(params, x1)
np.testing.assert_allclose(
    np.asarray(y1_ref, np.float32), np.asarray(y1_ep, np.float32),
    rtol=3e-2, atol=3e-2)
print("MOE-EP-OK")
"""


@pytest.mark.slow
def test_expert_parallel_moe_matches_dense_host():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MOE-EP-OK" in out.stdout
