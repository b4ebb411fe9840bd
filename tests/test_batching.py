"""Pair coalescing: planner ordering, FedEEC batched-execution parity, and
event-signature identity of batched vs serial scheduling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fedeec_paper import paper_setting
from repro.fl.api import WorkItem, create_algorithm
from repro.fl.engine import build_problem
from repro.obs.metrics import global_registry
from repro.sim.engine import SimEngine, plan_groups
from repro.sim.scenarios import get_scenario


def _small_cfg(**kw):
    return paper_setting(
        "synth_cifar10", 4, 2, samples_per_client=16, test_samples=64,
        image_size=8, embed_dim=16, edge_model="cnn2", cloud_model="cnn2",
        **kw,
    )


# --- plan_groups -------------------------------------------------------------


def _sig_of(table):
    return lambda it: table.get(it.node)


def test_plan_groups_coalesces_disjoint_same_signature():
    a = WorkItem("pair", node="a", peer="p1")
    b = WorkItem("pair", node="b", peer="p2")
    groups = plan_groups([a, b], _sig_of({"a": "X", "b": "X"}))
    assert groups == [[a, b]]


def test_plan_groups_shared_peer_serializes():
    a = WorkItem("pair", node="a", peer="p1")
    b = WorkItem("pair", node="b", peer="p1")  # conflicts with a via p1
    c = WorkItem("pair", node="c", peer="p2")
    groups = plan_groups([a, b, c], _sig_of({"a": "X", "b": "X", "c": "X"}))
    # b must trail a; c rides a's group
    assert groups == [[a, c], [b]]


def test_plan_groups_no_overtaking_later_groups():
    # c's signature matches group 1 but c conflicts with b in group 2 —
    # joining group 1 would dispatch c BEFORE the earlier-enabled b, so the
    # planner must open a new trailing group instead
    a = WorkItem("pair", node="a", peer="p1")
    b = WorkItem("pair", node="b", peer="p2")
    c = WorkItem("pair", node="c", peer="p2")
    groups = plan_groups([a, b, c], _sig_of({"a": "X", "b": "Y", "c": "X"}))
    assert groups == [[a], [b], [c]]


def test_plan_groups_none_signature_is_singleton():
    a = WorkItem("pair", node="a", peer="p1")
    b = WorkItem("pair", node="b", peer="p2")
    groups = plan_groups([a, b], _sig_of({}))
    assert groups == [[a], [b]]


def test_plan_groups_empty_peer_never_coalesces():
    # peer-less items share the scheduler's ready[""] slot — they serialize
    # in the serial engine, so they must conflict here too
    a = WorkItem("local", node="a")
    b = WorkItem("local", node="b")
    groups = plan_groups([a, b], _sig_of({"a": "X", "b": "X"}))
    assert groups == [[a], [b]]


# --- FedEEC signatures -------------------------------------------------------


def _fedeec(cfg):
    _, tree, client_data, auto = build_problem(cfg)
    return create_algorithm("fedeec", cfg, tree, client_data, auto)


def test_fedeec_batch_signature_groups_same_shape_pairs():
    trainer = _fedeec(_small_cfg())
    items = [it for it in trainer.work_items(0, lambda v: True)
             if it.node in trainer.client_data]
    sigs = [trainer.batch_signature(it) for it in items]
    assert all(s is not None for s in sigs)
    # the dirichlet partition varies shard sizes (and so step counts), but
    # same-shape pairs under different edges must still share a signature
    assert any(
        sigs[i] == sigs[j] and items[i].peer != items[j].peer
        for i in range(len(items)) for j in range(i + 1, len(items))
    )
    # edge items pair a different architecture against the cloud
    edge_items = [it for it in trainer.work_items(0, lambda v: True)
                  if it.node not in trainer.client_data]
    assert all(trainer.batch_signature(it) not in sigs for it in edge_items)


class _ConstRng:
    """rng stub whose draws depend only on (n, size) — serial and batched
    execution then consume identical per-pair indices regardless of global
    draw order, making their numerics directly comparable."""

    def choice(self, n, size, replace):
        rng = np.random.default_rng(n * 131 + size)
        return rng.choice(n, size=size, replace=replace)


def _max_leaf_diff(x, y):
    return max(
        float(np.max(np.abs(np.asarray(u) - np.asarray(v))))
        for u, v in zip(jax.tree_util.tree_leaves(x), jax.tree_util.tree_leaves(y))
    )


def test_fedeec_execute_batch_matches_serial():
    cfg = _small_cfg()
    a, b = _fedeec(cfg), _fedeec(cfg)
    a.rng, b.rng = _ConstRng(), _ConstRng()
    items = [it for it in a.work_items(0, lambda v: True)
             if it.node in a.client_data]
    group = max(plan_groups(items, a.batch_signature), key=len)
    assert len(group) >= 2  # one client per edge coalesces

    for it in group:
        a.execute(it)
    b.execute_batch(group)

    nodes = {it.node for it in group} | {it.peer for it in group}
    for v in sorted(nodes):
        assert _max_leaf_diff(a.params[v], b.params[v]) < 1e-5, v
        assert _max_leaf_diff(a.opt[v], b.opt[v]) < 1e-5, v
        assert _max_leaf_diff(a.skr[v], b.skr[v]) < 1e-5, v
    assert a.comm.summary() == b.comm.summary()


def _coalesced_group(trainer):
    items = [it for it in trainer.work_items(0, lambda v: True)
             if it.node in trainer.client_data]
    group = max(plan_groups(items, trainer.batch_signature), key=len)
    assert len(group) >= 2
    return group


def _stack_calls():
    reg = global_registry()
    return tuple(reg.counter("fl_stack_calls_total", step=s).value
                 for s in ("stack", "unstack"))


def _eager_stack(*groups):
    return tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *trees) for trees in groups)


def _eager_unstack(*stacked):
    B = jax.tree.leaves(stacked)[0].shape[0]
    return tuple([jax.tree.map(lambda x, b=b: x[b], tree) for b in range(B)]
                 for tree in stacked)


def _assert_trees_equal(x, y, what):
    assert jax.tree.structure(x) == jax.tree.structure(y), what
    for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
        assert u.dtype == v.dtype and u.shape == v.shape, what
        assert np.array_equal(np.asarray(u), np.asarray(v)), what


def test_compiled_stack_matches_eager_bitwise_once_per_direction():
    """The coalesced group's state moves in and out of stacked form by one
    compiled program each way per direction, and every node's params, opt
    and SKR state after the group are bitwise what per-leaf eager
    ``jnp.stack`` and ``x[b]`` give for the same draws."""
    cfg = _small_cfg()
    eager, compiled = _fedeec(cfg), _fedeec(cfg)
    eager.rng, compiled.rng = _ConstRng(), _ConstRng()
    eager._stack_fn = lambda: _eager_stack
    eager._unstack_fn = lambda: _eager_unstack
    group = _coalesced_group(compiled)

    eager.execute_batch(group)
    before = _stack_calls()
    compiled.execute_batch(group)
    assert tuple(a - b for a, b in zip(_stack_calls(), before)) == (2, 2)

    nodes = {it.node for it in group} | {it.peer for it in group}
    for v in sorted(nodes):
        _assert_trees_equal(eager.params[v], compiled.params[v], ("params", v))
        _assert_trees_equal(eager.opt[v], compiled.opt[v], ("opt", v))
        _assert_trees_equal(eager.skr[v], compiled.skr[v], ("skr", v))
    assert eager.comm.summary() == compiled.comm.summary()

    # a group of one runs the serial path and stacks nothing
    before = _stack_calls()
    compiled.execute_batch(group[:1])
    assert _stack_calls() == before


def test_decode_of_a_stacked_batch_is_the_flat_decode_reshaped():
    """A coalesced group's (B, bs, E) embeddings decode inside one program
    exactly as their flat (B * bs, E) batch does."""
    trainer = _fedeec(_small_cfg())
    dec = trainer._decode_fn()
    e = jax.random.normal(jax.random.PRNGKey(1), (3, 4, trainer.cfg.embed_dim))
    flat = dec(e.reshape(12, -1))
    _assert_trees_equal(dec(e), flat.reshape((3, 4) + flat.shape[1:]), "decode")


def test_second_execute_batch_of_a_signature_compiles_nothing():
    trainer = _fedeec(_small_cfg())
    group = _coalesced_group(trainer)
    trainer.execute_batch(group)

    compiles = []

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        trainer.execute_batch(group)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


# --- scheduler identity ------------------------------------------------------


@pytest.mark.parametrize("scenario", ["stable", "flash_crowd"])
def test_sim_signature_identical_batched_vs_serial(scenario):
    cfg = _small_cfg()

    def run(force_serial):
        trainer = _fedeec(cfg)
        if force_serial:
            trainer.batch_signature = lambda item: None
        engine = SimEngine(trainer, get_scenario(scenario), seed=cfg.seed)
        log = engine.run(2)
        return log.signature(), dict(engine.dispatch_stats)

    sig_batched, stats_batched = run(force_serial=False)
    sig_serial, stats_serial = run(force_serial=True)
    assert sig_batched == sig_serial
    assert stats_serial["batched_dispatches"] == 0
    assert stats_batched["batched_items"] > 0
    assert stats_batched["dispatches"] < stats_batched["items"]
    assert stats_batched["items"] == stats_serial["items"]
