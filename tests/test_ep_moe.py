"""Expert parallelism (shard_map + all_to_all) vs the single-device MoE.

Runs on 8 placeholder host devices — must execute before any other test
initializes jax with 1 device, hence the subprocess isolation.
"""
import json
import subprocess
import sys

import pytest

# 8-device shard_map subprocess — by far the suite's longest setup
# (minutes of XLA host-platform compilation); nightly lane
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.arch import FFNSpec
from repro.models.moe import init_moe, moe_ffn
from repro.dist.ep_moe import ep_moe_ffn

mesh = jax.make_mesh((2, 4), ('data', 'model'))
key = jax.random.PRNGKey(0)
x = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P('model', None)))
res = {}
for e, k in [(8, 2), (6, 2), (16, 4)]:
    f = FFNSpec(kind='moe', d_ff=32, activation='swiglu', n_experts=e,
                top_k=k)
    params = init_moe(key, 64, f, dtype=jnp.float32)
    ref, _ = moe_ffn(params, f, x)
    out = ep_moe_ffn(params, f, xs, mesh, capacity_factor=8.0)
    res[f'e{e}_k{k}'] = float(jnp.max(jnp.abs(np.asarray(out)
                                              - np.asarray(ref))))
# capacity drops: tiny capacity must still run and produce finite output
f = FFNSpec(kind='moe', d_ff=32, activation='swiglu', n_experts=8, top_k=2)
params = init_moe(key, 64, f, dtype=jnp.float32)
out = ep_moe_ffn(params, f, xs, mesh, capacity_factor=0.25)
res['drops_finite'] = bool(jnp.all(jnp.isfinite(out)))
print('RESULT::' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def ep_results():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=480,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT::")][0]
    return json.loads(line[len("RESULT::"):])


def test_ep_matches_reference_divisible(ep_results):
    assert ep_results["e8_k2"] < 1e-4
    assert ep_results["e16_k4"] < 1e-4


def test_ep_matches_reference_padded_experts(ep_results):
    assert ep_results["e6_k2"] < 1e-4


def test_ep_capacity_drops_are_safe(ep_results):
    assert ep_results["drops_finite"]
