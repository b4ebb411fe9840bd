"""The benchmark's yardstick on its own: discovery of cells by name, the
peaks table, operation counts against XLA's, and the trace reduction."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from bench_tiny import REPO

from bench import flops, harness, reference, spec, trace_reduce
from repro.models import autoencoder, cnn
from repro.models.registry import FL_MODELS

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
FIXTURE = Path(__file__).parent / "fixtures" / "probe.xplane.pb"


# -- discovery ---------------------------------------------------------------


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_is_found_by_name(name):
    cell = spec.load_cell(name)
    fl = cell.config["fl_config"]
    for tier in ("end_model", "edge_model", "cloud_model"):
        assert fl[tier] in cell.config["models"]
    assert {"scenario", "eval_every", "warmup_rounds"} <= set(cell.traffic)
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


def test_a_new_cell_needs_only_files_and_an_entry():
    bench = json.loads(json.dumps(BENCHMARK))
    old = bench["workloads"][0]
    bench["workloads"].append(dict(old, name=old["name"] + "-copy"))
    limits = spec.BENCH / "limits" / f"{old['name']}-copy.json"
    shutil.copy(spec.BENCH / "limits" / f"{old['name']}.json", limits)
    try:
        cell = spec.load_cell(old["name"] + "-copy", bench)
    finally:
        limits.unlink()
    assert cell.config == spec.load_cell(old["name"]).config


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCHMARK["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    read = spec.metric_reader(metric)
    record = {"trainer_init_s": 1.0, "eval_s": [0.5], "setup_compile_s": 2.0,
              "compiles_in_window": 0, "dispatches_per_round": 15.0}
    value = read(record)
    assert value is None or value >= 0
    traced = dict(record, sched_ms=3.0, algo_ms=4.0,
                  span_ms={"bsbodp.student": 1.5, "bsbodp.unstack": 0.5,
                           "bsbodp.inputs": 0.7, "round": 9.0},
                  counters={'fl_jit_calls_total{step="decode"}': 300.0,
                            'fl_jit_calls_total{step="student"}': 300.0,
                            'fl_stack_calls_total{step="stack"}': 20.0},
                  memory_peak_bytes=2_040_982_016, peaks=spec.peaks("TPU v5 lite"),
                  trace={"ops": 1e9, "window_s": 2.0, "busy_s": 0.5, "launches": 8,
                         "rounds": 1,
                         "by_program": {"jit_bsbodp_student": {"device_s": 0.25,
                                                               "launches": 2}},
                         "work": {"resnet10": {"student": {"flops": 1e9, "bytes": 1e8}}}})
    assert read(traced) >= 0


@pytest.mark.parametrize("metric,value", [
    ("peak_hbm_gb", 2.040982016),
    # least time 0.25 s (bytes) + 0.04 s (operations) of 0.5 s on the device
    ("bsbodp_student_roofline", 58.0)])
def test_peak_memory_and_the_student_roofline_read_a_made_up_record(metric, value):
    read = spec.metric_reader(metric)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    record = {"memory_peak_bytes": 2_040_982_016, "peaks": peaks, "trace": {
        "rounds": 1,
        "by_program": {"jit_bsbodp_student": {"device_s": 0.5, "launches": 20},
                       "jit_bsbodp_teacher": {"device_s": 9.0, "launches": 20}},
        "work": {"resnet10": {"student": {"flops": 1e10, "bytes": 2.5e8},
                              "teacher": {"flops": 9e12, "bytes": 9e12}},
                 "resnet18": {"student": {"flops": 4e10, "bytes": 1e6}},
                 "cnn1": {"student_leaf": {"flops": 9e12, "bytes": 9e12}}}}}
    assert read(record) == pytest.approx(value)
    assert read({"memory_peak_bytes": 0, "trace": {"rounds": 1, "by_program": {},
                                                   "work": {}}}) is None


@pytest.mark.parametrize("metric,value", [
    ("student_host_ms_per_round", 1.5), ("unstack_host_ms_per_round", 0.5),
    ("inputs_host_ms_per_round", 0.7), ("jit_step_calls_per_round", 600.0),
    ("student_device_ms_per_round", 250.0)])
def test_the_programs_spans_counters_and_programs_are_read(metric, value):
    read = spec.metric_reader(metric)
    record = {"span_ms": {"bsbodp.student": 1.5, "bsbodp.unstack": 0.5,
                          "bsbodp.inputs": 0.7},
              "counters": {'fl_jit_calls_total{step="decode"}': 300.0,
                           'fl_jit_calls_total{step="student"}': 300.0,
                           'fl_stack_calls_total{step="stack"}': 20.0},
              "trace": {"rounds": 2, "by_program": {
                  "jit_bsbodp_student": {"device_s": 0.4, "launches": 2},
                  "jit_bsbodp_student_leaf": {"device_s": 0.1, "launches": 1},
                  "jit_bsbodp_teacher": {"device_s": 9.0, "launches": 3}}}}
    assert read(record) == pytest.approx(value)
    assert read({"trace": {"rounds": 1, "by_program": {}}}) is None


# -- model families ------------------------------------------------------------

C50E5 = spec.load_json(spec.BENCH / "configs" / "fedeec-cifar10-c50e5.json")


@pytest.mark.parametrize("model,ops", [("cnn1", 1_471_680), ("resnet10_w64", 448_540_160),
                                       ("resnet18_w64", 963_718_656)])
def test_operations_by_family_are_the_c50e5_models_counts(model, ops):
    assert flops.forward_flops(C50E5["models"][model], 32, 10) == ops


@pytest.mark.parametrize("family,spec_", [
    ("cnn", {"family": "cnn", "widths": [8, 16, 32]}),
    ("resnet", {"family": "resnet", "blocks_per_stage": [2, 1], "width": 16})])
def test_parameter_counts_are_the_programs(family, spec_):
    harness.register_models({"models": {f"count_{family}": spec_}})
    init, _ = FL_MODELS.pop(f"count_{family}")
    params = init(jax.random.PRNGKey(0), 10, 16)
    assert flops.param_count(spec_, 16, 10) == sum(x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("lookup", [
    lambda m: harness.register_models({"models": {"no_such_model": m}}),
    lambda m: reference.tier_model(m, 10, 32),
    lambda m: flops.forward_flops(m, 32, 10)],
    ids=["register_models", "tier_model", "forward_flops"])
def test_an_unknown_family_is_an_error(lookup):
    with pytest.raises(KeyError, match="cnn"):
        lookup({"family": "no_such_family"})


NEW_FAMILY = """
import jax.numpy as jnp


def register(name, spec, registry):
    registry[name] = (lambda key, num_classes=10, image=16: {"w": jnp.ones((3, num_classes))},
                      lambda p, x: x.mean(axis=(1, 2)) @ p["w"])


def reference(spec, num_classes, image):
    return (lambda key: {"w": jnp.ones((3, num_classes))},
            lambda p, x: x.mean(axis=(1, 2)) @ p["w"])


def forward_flops(spec, image, num_classes):
    return spec["ops"]
"""


def test_a_new_family_needs_only_a_file():
    path = spec.BENCH / "families" / "made_up_family.py"
    path.write_text(NEW_FAMILY)
    m = {"family": "made_up_family", "ops": 1234}
    try:
        harness.register_models({"models": {"made_up_model": m}})
        init, apply = reference.tier_model(m, 10, 8)
        ops = flops.forward_flops(m, 8, 10)
        prog_init, prog_apply = FL_MODELS.pop("made_up_model")
    finally:
        path.unlink()
        spec.family.cache_clear()
    assert ops == 1234
    x = jnp.ones((2, 8, 8, 3))
    assert apply(init(jax.random.PRNGKey(0)), x).shape == (2, 10)
    assert prog_apply(prog_init(jax.random.PRNGKey(0)), x).shape == (2, 10)


# -- peaks -------------------------------------------------------------------


def test_peaks_are_keyed_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_a_device_missing_from_the_peaks_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("cpu")


# -- operations ----------------------------------------------------------------


def _xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("size,k,stride", [(16, 3, 1), (16, 3, 2), (15, 3, 2), (16, 1, 2)])
def test_conv_operations_match_xla(size, k, stride):
    x, w = jnp.ones((1, size, size, 8)), jnp.ones((k, k, 8, 12))
    assert flops.conv_flops(size, k, 8, 12, stride) == _xla_flops(
        lambda x, w: cnn.conv(x, w, stride), x, w)


def test_dense_operations_match_xla():
    a, b = jnp.ones((1, 40)), jnp.ones((40, 10))
    assert flops.dense_flops(40, 10) == _xla_flops(lambda a, b: a @ b, a, b)


@pytest.mark.parametrize("model", ["cnn", "resnet", "decoder"])
def test_model_operations_are_xlas_without_the_elementwise_ones(model):
    x = jnp.ones((1, 16, 16, 3))
    if model == "cnn":
        p = cnn.init_cnn(jax.random.PRNGKey(0), 10, widths=(8, 16, 32), image=16)
        ours, xla = (flops.forward_flops({"family": "cnn", "widths": [8, 16, 32]}, 16, 10),
                     _xla_flops(cnn.apply_cnn, p, x))
    elif model == "resnet":
        p = cnn.init_resnet(jax.random.PRNGKey(0), 10, blocks_per_stage=(2, 1), width=16)
        ours = flops.forward_flops(
            {"family": "resnet", "blocks_per_stage": [2, 1], "width": 16}, 16, 10)
        xla = _xla_flops(lambda p, x: cnn.apply_resnet(p, x, (2, 1)), p, x)
    else:
        a = autoencoder.init_autoencoder(jax.random.PRNGKey(0), image=16, embed_dim=8)
        ours = flops.decode_flops(16, 8)
        xla = _xla_flops(lambda a, e: autoencoder.decode(a, e, 16), a, jnp.ones((1, 8)))
    assert 0.9 * xla <= ours <= xla


# -- trace reduction -------------------------------------------------------------


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[SimpleNamespace(name=n, start_ns=a, duration_ns=d)
                                         for n, a, d in evs])
        for ln, evs in lines.items()])


def test_reduction_of_a_made_up_trace():
    host = _plane("/host:CPU", {"python": [("bench.round", 1000, 9000),
                                           ("bench.clock", 500, 10),
                                           ("bench.eval", 8000, 2000)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("fusion", 1500, 1000), ("conv", 2000, 1000), ("conv", 6000, 500),
                    ("late", 9500, 1000)],
        "XLA Modules": [("jit_fn", 1400, 1700), ("jit_fn", 5900, 700), ("jit_g", 9400, 1200)]})
    # the program's span (perf_counter seconds) covers trace time 3000..6000
    r = trace_reduce.reduce_profile([host, dev], marker_perf=10.0,
                                    host_spans=[("execute", 10.0 + 2.5e-6, 10.0 + 5.5e-6)])
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["busy_s"] == pytest.approx((1500 + 500 + 500) * 1e-9)
    assert r["launches"] == 3
    assert r["device_ops"][0] == ["jit_fn/conv", pytest.approx(1.5e-6)]
    gaps = dict(r["idle_gaps"])
    assert gaps["execute (1 gaps)"] == pytest.approx(3e-6)
    assert gaps["eval (1 gaps)"] == pytest.approx(3e-6)
    assert gaps["harness (1 gaps)"] == pytest.approx(0.5e-6)
    assert r["by_program"] == {"jit_fn": {"device_s": pytest.approx(2.5e-6), "launches": 2},
                               "jit_g": {"device_s": pytest.approx(0.5e-6), "launches": 1}}


def test_union_and_gaps():
    busy = trace_reduce.union([(3, 5), (1, 2), (4, 7), (7, 8)])
    assert busy == [[1, 2], [3, 8]]
    assert trace_reduce.gaps(busy, 0, 10) == [(0, 1), (2, 3), (8, 10)]


def test_reduction_of_a_recorded_chip_trace():
    meta = json.loads((FIXTURE.parent / "probe.json").read_text())
    r = trace_reduce.reduce_file(str(FIXTURE), marker_perf=meta["marker"],
                                 host_spans=[("probe", meta["marker"], meta["marker"] + 1.0)])
    for k, v in meta["expect"].items():
        assert r[k] == pytest.approx(v), k
    assert 0 < r["busy_s"] < r["window_s"]
    assert {name.split("/")[0] for name, _ in r["device_ops"]} == {"jit__lambda"}
    assert dict(r["idle_gaps"])["eval (1 gaps)"] > 0


# -- the command -------------------------------------------------------------------


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           BENCHMARK["workloads"][0]["name"], "--seed", "1",
                           "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_the_cpu_and_prints_no_result():
    p = _run(REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_fails_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
