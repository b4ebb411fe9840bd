"""Launcher-layer integration tests: the LM trainer, the batched server,
and the 512-virtual-device dry-run itself (in a subprocess, honoring the
XLA-flag-before-jax-import contract)."""
import os
import subprocess
import sys

import numpy as np


def test_train_lm_loss_decreases():
    from repro.launch.train import train_lm

    losses = train_lm("llama3.2-3b", steps=12, batch=4, seq=64, log_every=100)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # synthetic bigram structure is learnable


def test_serve_batched_decode():
    from repro.launch.serve import serve

    gen = serve("rwkv6-1.6b", num_requests=3, prompt_len=4, gen_len=4,
                cache_len=16)
    assert gen.shape == (3, 4)
    assert (gen >= 0).all()


def test_checkpoint_full_model_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import load_pytree, save_pytree
    from repro.configs import get_arch, reduced
    from repro.models import ModelOpts, init_params

    cfg = reduced(get_arch("qwen2-moe-a2.7b"))
    params = init_params(jax.random.PRNGKey(0), cfg, ModelOpts(remat=False))
    path = os.path.join(tmp_path, "model.msgpack")
    save_pytree(path, params)
    back = load_pytree(path)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32))


DRYRUN_SCRIPT = r"""
from repro.launch.dryrun import run_one
rec = run_one("whisper-small", "prefill_32k", out_dir="")
assert rec["status"] == "ok", rec
assert rec["num_devices"] == 256
assert rec["memory"]["temp_bytes"] > 0
rec2 = run_one("rwkv6-1.6b", "long_500k", multi_pod=True, out_dir="")
assert rec2["status"] == "ok" and rec2["num_devices"] == 512
rec3 = run_one("whisper-small", "long_500k", out_dir="")
assert rec3["status"] == "skipped"
print("DRYRUN_OK")
"""


def test_dryrun_lowers_on_production_mesh():
    """The deliverable-(e) path, exercised end to end on two meshes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child runs on virtual CPU devices; it must never reach for a chip
    # that this test process may hold
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", DRYRUN_SCRIPT],
        capture_output=True, text=True, env=env, timeout=480,
    )
    assert "DRYRUN_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-2000:]
