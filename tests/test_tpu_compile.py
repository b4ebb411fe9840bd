"""Compile FedEEC's steps and the Pallas kernels for one TPU v5e chip.

Nothing runs: each program is lowered from ``ShapeDtypeStruct``s placed on
a described (not attached) v5e device and compiled by the TPU compiler,
which refuses what the chip would refuse (misaligned kernel blocks, too
much VMEM, programs that do not fit). The topology is described inside a
fixture, so that only the worker that runs this file loads the TPU
library; where it cannot be described, the tests skip from there.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# the chip_smoke.py configuration: 32x32 images, batch 8, 10 classes
IMAGE, BATCH, CLASSES, STACK = 32, 8, 10, 4
GROUP = 5  # the leaf groups of the benchmark's c50e5 cell
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def trainer():
    """A FedEEC trainer on a 2-client, 1-edge tree with the default tier
    ladder (cnn1 -> resnet10 -> resnet18); only its jitted steps and its
    parameter shapes are used."""
    from repro.configs.base import FLConfig
    from repro.core.topology import Tree
    from repro.fl.api import create_algorithm
    from repro.models.autoencoder import init_autoencoder

    cfg = FLConfig(num_clients=2, num_edges=1, image_size=IMAGE,
                   batch_size=BATCH, num_classes=CLASSES)
    rng = np.random.default_rng(0)
    client_data = {
        f"client{i}": (rng.random((BATCH, IMAGE, IMAGE, 3), np.float32),
                       rng.integers(0, CLASSES, BATCH).astype(np.int32))
        for i in range(cfg.num_clients)
    }
    auto = init_autoencoder(jax.random.PRNGKey(0), image=IMAGE,
                            embed_dim=cfg.embed_dim)
    return create_algorithm("fedeec", cfg, Tree.three_tier(1, 2),
                            client_data, auto)


def _spec(tree, sharding, stack: int = 0):
    lead = (stack,) if stack else ()
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(lead + tuple(np.shape(x)),
                                       jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _compile(fn, *specs):
    compiled = fn.lower(*specs).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    return compiled


NODE = {"cnn1": "client0", "resnet10": "edge0", "resnet18": "cloud"}


def _batch_specs(sharding, stack: int = 0):
    lead = (stack,) if stack else ()
    img = jax.ShapeDtypeStruct(lead + (BATCH, IMAGE, IMAGE, 3), jnp.float32,
                               sharding=sharding)
    lab = jax.ShapeDtypeStruct(lead + (BATCH,), jnp.int32, sharding=sharding)
    tq = jax.ShapeDtypeStruct(lead + (BATCH, CLASSES), jnp.float32,
                              sharding=sharding)
    return img, lab, tq


@pytest.mark.parametrize("model", ["cnn1", "resnet10", "resnet18"])
def test_teacher_step_compiles(trainer, one_chip, model):
    v = NODE[model]
    img, lab, _ = _batch_specs(one_chip)
    _compile(trainer._teacher_fn(model), _spec(trainer.params[v], one_chip),
             _spec(trainer.skr[v], one_chip), img, lab)


@pytest.mark.parametrize("model,leaf", [("cnn1", True), ("resnet10", False),
                                        ("resnet18", False)])
def test_student_step_compiles(trainer, one_chip, model, leaf):
    v = NODE[model]
    img, lab, tq = _batch_specs(one_chip)
    args = [_spec(trainer.params[v], one_chip), _spec(trainer.opt[v], one_chip),
            img, lab, tq]
    if leaf:
        args += [img, lab]
    _compile(trainer._student_fn(model, leaf), *args)


def test_decode_step_compiles(trainer, one_chip):
    e = jax.ShapeDtypeStruct((BATCH, trainer.cfg.embed_dim), jnp.float32,
                             sharding=one_chip)
    _compile(trainer._decode_fn(), e)


def test_coalesced_leaf_pair_compiles(trainer, one_chip):
    """The jit(vmap) steps of STACK coalesced leaf pairs, child as student:
    resnet10 edges teach cnn1 clients."""
    img, lab, tq = _batch_specs(one_chip, STACK)
    _compile(trainer._teacher_fn_batched("resnet10"),
             _spec(trainer.params["edge0"], one_chip, STACK),
             _spec(trainer.skr["edge0"], one_chip, STACK), img, lab)
    _compile(trainer._student_fn_batched("cnn1", True),
             _spec(trainer.params["client0"], one_chip, STACK),
             _spec(trainer.opt["client0"], one_chip, STACK),
             img, lab, tq, img, lab)


def test_coalesced_group_stack_and_unstack_compile(trainer, one_chip):
    """The programs that move a group of GROUP coalesced pairs' state into
    stacked form and back, edge as student: cnn1 clients teach ResNet-10
    edges at base width 64 (the benchmark's ladder) with their AdamW state."""
    from repro.models.cnn import init_resnet
    from repro.optim import adamw_init

    params = init_resnet(jax.random.PRNGKey(0), CLASSES, width=64)
    opt = adamw_init(params)
    teacher, skr = trainer.params["client0"], trainer.skr["client0"]
    _compile(trainer._stack_fn(),
             *[[_spec(t, one_chip)] * GROUP for t in (teacher, skr, params, opt)])
    _compile(trainer._unstack_fn(),
             *[_spec(t, one_chip, GROUP) for t in (skr, params, opt)])


# -- Pallas kernels at the BENCH_kernels.json shapes -------------------------


def _kernel_cases():
    from repro.kernels.distill_loss import distill_loss_batched
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rwkv6_scan import rwkv6_scan
    from repro.kernels.skr_rectify import skr_rectify_batched

    f32, i32 = jnp.float32, jnp.int32
    B, N, V = 4, 256, 2048
    distill = [((B, N, V), f32), ((B, N, V), f32), ((B, N), i32)]
    rwkv = [((2, 256, 4, 32), f32)] * 4 + [((4, 32), f32),
                                            ((2, 4, 32, 32), f32)]
    return {
        "distill_loss": (
            lambda z, t, y: distill_loss_batched(z, t, y, 1.5, 1.0, False),
            distill),
        "distill_loss_grad": (
            jax.grad(lambda z, t, y: distill_loss_batched(
                z, t, y, 1.5, 1.0, False).sum()),
            distill),
        "skr_rectify": (
            lambda p, y, q, c: skr_rectify_batched(p, y, q, c, interpret=False),
            [((4, 256, 1024), f32), ((4, 256), i32), ((4, 1024), f32),
             ((4, 1024), i32)]),
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            [((2, 512, 8, 64), f32), ((2, 512, 2, 64), f32),
             ((2, 512, 2, 64), f32)]),
        "rwkv6_scan": (
            lambda *a: rwkv6_scan(*a, chunk=64, interpret=False), rwkv),
    }


@pytest.mark.parametrize("name", ["distill_loss", "distill_loss_grad",
                                  "skr_rectify", "flash_attention",
                                  "rwkv6_scan"])
def test_pallas_kernel_compiles(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = _compile(jax.jit(fn), *specs)
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} compiled without its Pallas kernel"
