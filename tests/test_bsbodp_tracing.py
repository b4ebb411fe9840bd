"""BSBODP on the profiler's clock: the tracer's spans mirrored as
``repro.<name>`` profiler annotations, the ``bsbodp`` step-phase spans,
the named jitted steps, and the always-on call and host-to-device counters."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.fedeec_paper import paper_setting
from repro.fl.api import create_algorithm
from repro.fl.engine import build_problem
from repro.obs.metrics import global_registry
from repro.obs.trace import Tracer, tracing
from repro.sim.engine import SimEngine
from repro.sim.scenarios import get_scenario

STEPS = 2


def _host_events(path) -> list[tuple[str, float, float]]:
    from jax.profiler import ProfileData

    out = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name.startswith("/host"):
            for line in pl.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    return out


def _profiled(tmp_path, fn) -> list[tuple[str, float, float]]:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    return [e for e in _host_events(path) if e[0].startswith("repro.")]


# -- the tracer's spans on the profiler's host plane ---------------------------


def test_installed_tracers_spans_land_on_the_host_plane_nested(tmp_path):
    tr = Tracer()

    def run():
        with tracing(tr):
            with tr.span("outer", cat="round", round=3):
                with tr.span("inner", cat="bsbodp"):
                    jnp.ones(4).block_until_ready()

    events = {name: (a, b) for name, a, b in _profiled(tmp_path, run)}
    assert set(events) == {"repro.outer", "repro.inner"}  # bare names, args left out
    (oa, ob), (ia, ib) = events["repro.outer"], events["repro.inner"]
    assert oa <= ia < ib <= ob
    assert [sp.name for sp in tr.spans] == ["outer", "inner"]
    assert tr.spans[0].args == {"round": 3}


def test_a_tracer_not_installed_leaves_nothing_on_the_trace(tmp_path):
    tr = Tracer()

    def run():
        with tr.span("outer"):
            with tr.span("inner"):
                pass

    assert _profiled(tmp_path, run) == []
    assert len(tr.spans) == 2


# -- one round of a small tree ---------------------------------------------------


def _cfg():
    return paper_setting("synth_cifar10", 4, 2, samples_per_client=16, test_samples=64,
                         image_size=8, embed_dim=16, edge_model="cnn2",
                         cloud_model="cnn2", distill_steps=STEPS)


@pytest.fixture(scope="module")
def problem():
    return build_problem(_cfg())


def _round(problem, tracer):
    cfg = _cfg()
    _, tree, client_data, auto = problem
    trainer = create_algorithm("fedeec", cfg, tree, client_data, auto)
    engine = SimEngine(trainer, get_scenario("stable"), seed=cfg.seed, tracer=tracer)
    reg = global_registry()
    calls0 = {s: reg.counter("fl_jit_calls_total", step=s).value
              for s in ("decode", "teacher", "student")}
    bytes0 = reg.counter("fl_h2d_bytes_total").value
    engine.run(1)
    calls = {s: reg.counter("fl_jit_calls_total", step=s).value - v
             for s, v in calls0.items()}
    return trainer, engine, calls, reg.counter("fl_h2d_bytes_total").value - bytes0


@pytest.fixture(scope="module")
def traced(problem):
    tracer = Tracer()
    return (tracer, *_round(problem, tracer))


def test_the_round_has_a_coalesced_group_and_a_serial_pair(traced):
    _, _, engine, _, _ = traced
    batched = engine.metrics.counter("sim_batched_dispatches_total").value
    assert 0 < batched < engine.metrics.counter("sim_dispatches_total").value


def test_bsbodp_spans_nest_under_execute(traced):
    tracer = traced[0]
    by_id = {sp.sid: sp for sp in tracer.spans}
    bsbodp = [sp for sp in tracer.spans if sp.cat == "bsbodp"]
    directions = [sp for sp in bsbodp if sp.name == "bsbodp.direction"]
    assert directions
    for sp in directions:
        assert by_id[sp.parent].name in ("execute", "execute_batch")
        assert sp.args["steps"] == STEPS and sp.args["B"] >= 1
        assert {"student", "teacher"} <= set(sp.args)
    for sp in bsbodp:
        if sp.name != "bsbodp.direction":
            assert by_id[sp.parent].name == "bsbodp.direction"
    names = {sp.name for sp in bsbodp}
    assert names == {"bsbodp.direction", "bsbodp.inputs", "bsbodp.decode", "bsbodp.teacher",
                     "bsbodp.student", "bsbodp.stack", "bsbodp.unstack"}
    # stacking only where the group is coalesced
    for sp in directions:
        kids = {k.name for k in bsbodp if k.parent == sp.sid}
        assert ("bsbodp.stack" in kids) == (sp.args["B"] > 1)
        assert sum(k.name == "bsbodp.decode" for k in bsbodp if k.parent == sp.sid) == STEPS


def test_jit_calls_counter_counts_three_steps_of_every_direction(traced):
    _, _, engine, calls, _ = traced
    groups = engine.metrics.counter("sim_dispatches_total").value
    assert calls == {s: groups * 2 * STEPS for s in ("decode", "teacher", "student")}
    assert sum(calls.values()) == 3 * groups * 2 * STEPS


def test_h2d_bytes_counter_counts_the_arrays_sent(traced):
    _, trainer, engine, _, nbytes = traced
    expect = 0
    for child, _parent in trainer.round_pairs():
        eps, labels = trainer.embeddings[child]
        bs = min(trainer.cfg.batch_size, len(labels))
        # both directions: the bridge embeddings and their labels
        expect += 2 * STEPS * bs * (eps[0].nbytes + labels.itemsize)
        if child in trainer.client_data:  # the leaf as student: its local batch
            lx, ly = trainer.client_data[child]
            expect += STEPS * min(bs, len(ly)) * (lx[0].nbytes + ly.itemsize)
    assert nbytes == expect


def test_tracing_leaves_the_event_log_as_it_was(problem, traced):
    _, _, engine, calls, nbytes = traced
    _, plain, plain_calls, plain_bytes = _round(problem, None)
    assert plain.log.signature() == engine.log.signature()
    assert (plain_calls, plain_bytes) == (calls, nbytes)


# -- the named jitted steps --------------------------------------------------------


def test_each_bsbodp_step_lowers_to_a_named_module(problem):
    cfg = _cfg()
    _, tree, client_data, auto = problem
    t = create_algorithm("fedeec", cfg, tree, client_data, auto)
    leaf, edge, cloud = "client0", tree.parent["client0"], tree.root
    bs, B = cfg.batch_size, 2
    e = jnp.zeros((bs, cfg.embed_dim))
    y = jnp.zeros((bs,), jnp.int32)
    x = t._decode_fn()(e)
    q = jnp.zeros((bs, cfg.num_classes))
    lx = jnp.zeros((bs,) + client_data[leaf][0].shape[1:])

    def stack(tree_, n=B):
        return jax.tree.map(lambda a: jnp.stack([a] * n), tree_)

    def module(fn, *args):
        return fn.lower(*args).as_text().split("module @", 1)[1].split(" ", 1)[0]

    m = t.model_of
    assert module(t._decode_fn(), e) == "jit_bsbodp_decode"
    assert module(t._decode_fn(), stack(e)) == "jit_bsbodp_decode"
    assert module(t._teacher_fn(m[cloud]), t.params[cloud], t.skr[cloud], x, y) == \
        "jit_bsbodp_teacher"
    assert module(t._teacher_fn_batched(m[edge]), stack(t.params[edge]), stack(t.skr[edge]),
                  stack(x), stack(y)) == "jit_bsbodp_teacher"
    assert module(t._student_fn(m[edge], False), t.params[edge], t.opt[edge], x, y, q) == \
        "jit_bsbodp_student"
    assert module(t._student_fn(m[leaf], True), t.params[leaf], t.opt[leaf], x, y, q,
                  lx, y) == "jit_bsbodp_student_leaf"
    assert module(t._student_fn_batched(m[leaf], True), stack(t.params[leaf]),
                  stack(t.opt[leaf]), stack(x), stack(y), stack(q), stack(lx), stack(y)) == \
        "jit_bsbodp_student_leaf"
    group = [[t.params[edge]] * B, [t.skr[edge]] * B, [t.params[leaf]] * B, [t.opt[leaf]] * B]
    assert module(t._stack_fn(), *group) == "jit_bsbodp_stack"
    assert module(t._unstack_fn(), stack(t.skr[edge]), stack(t.params[leaf]),
                  stack(t.opt[leaf])) == "jit_bsbodp_unstack"
