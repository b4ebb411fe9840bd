"""shard_map hierarchical aggregation == flat global mean (multi-device)."""
import os
import subprocess
import sys

import jax.numpy as jnp

from repro.sharding.hierarchy import hier_grad_mean


def test_single_device_fallback():
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((1, 1), ("data", "model"))
    x = {"w": jnp.arange(12.0).reshape(4, 3)}
    out = hier_grad_mean(x, mesh)
    assert jnp.allclose(out["w"], x["w"].mean(0))


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.hierarchy import hier_grad_mean, edge_only_mean

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(0)
x = {"w": jnp.asarray(rng.normal(0, 1, (8, 5)), jnp.float32),
     "b": jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32)}
with mesh:
    out = hier_grad_mean(x, mesh)
    assert jnp.allclose(out["w"], x["w"].mean(0), atol=1e-6), "staged != flat"
    assert jnp.allclose(out["b"], x["b"].mean(0), atol=1e-6)
    # edge-only: per-pod means differ and average to the global mean
    eo = edge_only_mean(x, mesh)
    assert eo["w"].shape == (2, 5)
    assert jnp.allclose(eo["w"].mean(0), x["w"].mean(0), atol=1e-6)
    pod0 = x["w"][:4].mean(0)
    assert jnp.allclose(eo["w"][0], pod0, atol=1e-6), "pod0 edge aggregate"
print("HIERARCHY_OK")
"""


def test_multidevice_staged_equals_flat():
    """Run in a subprocess with 8 virtual devices (the main test process
    keeps the single real CPU device per the dry-run import contract)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child runs on virtual CPU devices; it must never reach for a chip
    # that this test process may hold
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert "HIERARCHY_OK" in res.stdout, res.stdout + res.stderr
