"""The split of a round by the program's own spans, named steps and
counters (``bench/split.py``): ``by_program``, the ``repro.*`` labels of
the idle gaps, and what it reads from the program's tracer and counters."""
import random
import time
from pathlib import Path
from types import SimpleNamespace

import pytest
from bench_tiny import SEED, tiny_cell

from bench import harness, program_obs, split, trace_reduce
from repro.obs.trace import Tracer

FIXTURE = Path(__file__).parent / "fixtures" / "probe.xplane.pb"


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[SimpleNamespace(name=n, start_ns=a, duration_ns=d)
                                         for n, a, d in evs])
        for ln, evs in lines.items()])


DEVICE = _plane("/device:TPU:0", {
    "XLA Ops": [("fusion", 1500, 500), ("conv", 2000, 1000), ("copy", 6000, 500),
                ("late", 9500, 1000)],
    "XLA Modules": [("jit_bsbodp_teacher(1)", 1400, 1700), ("jit_broadcast_in_dim(2)", 5900, 700),
                    ("jit_apply(3)", 9400, 1200)]})


def _host(*program):
    return _plane("/host:CPU", {"python": [("bench.round", 1000, 9000), ("bench.clock", 500, 10),
                                           ("bench.eval", 8000, 2000), *program]})


def test_by_program_sums_each_programs_operations_and_launches():
    r = split.split_profile([_host(), DEVICE])
    assert r["by_program"] == {
        "jit_bsbodp_teacher": {"device_s": pytest.approx(1.5e-6), "launches": 1},
        "jit_broadcast_in_dim": {"device_s": pytest.approx(0.5e-6), "launches": 1},
        "jit_apply": {"device_s": pytest.approx(0.5e-6), "launches": 1}}
    red = trace_reduce.reduce_profile([_host(), DEVICE])
    assert sum(v["device_s"] for v in r["by_program"].values()) == pytest.approx(red["busy_s"])
    assert sum(v["launches"] for v in r["by_program"].values()) == red["launches"]
    assert r["eval_launches"] == 1


def test_program_events_label_the_gaps():
    host = _host(("repro.round 2", 1000, 6500), ("repro.execute_batch", 2500, 4000),
                 ("repro.bsbodp.direction", 2600, 3800), ("repro.bsbodp.stack", 4000, 1000),
                 ("repro.eval", 7900, 2200))
    idle = split.split_profile([host, DEVICE])["idle_by_label"]
    assert idle["bsbodp.stack"] == [pytest.approx(3e-6), 1]
    assert idle["eval"] == [pytest.approx(3e-6), 1]
    assert idle["scheduler"] == [pytest.approx(0.5e-6), 1]
    assert set(idle) == {"bsbodp.stack", "eval", "scheduler"}


def test_a_trace_without_program_events_labels_as_the_reduction_does():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(FIXTURE)).planes)
    r = split.split_profile(planes)
    red = trace_reduce.reduce_profile(planes)
    assert {f"{k} ({n} gaps)": v for k, (v, n) in r["idle_by_label"].items()} == pytest.approx(
        dict(red["idle_gaps"]))
    assert sum(v["launches"] for v in r["by_program"].values()) == red["launches"] == 4
    assert r["eval_launches"] == 1
    assert sum(v["device_s"] for v in r["by_program"].values()) >= red["busy_s"] * (1 - 1e-9)


@pytest.mark.parametrize("seed", range(4))
def test_labels_are_those_of_a_scan_over_every_span(seed):
    rng = random.Random(seed)
    spans = []
    for i in range(300):
        a = rng.randrange(0, 1000)
        spans.append((f"s{i}", float(a), float(a + rng.choice([1, 5, 5, 20, 200]))))
    ts = [rng.uniform(-10, 1300) for _ in range(500)] + [s[1] for s in spans[:50]]
    assert trace_reduce.labels(ts, spans) == [trace_reduce.label_of(t, spans) for t in ts]


def test_bsbodp_spans_are_summed_by_name_per_round():
    tr = Tracer()
    for r in (4, 5):
        with tr.span(f"round {r}", cat="round", round=r):
            with tr.span("execute_batch", cat="execute"):
                for _ in range(r):
                    with tr.span("bsbodp.inputs", cat="bsbodp"):
                        time.sleep(1e-3)
    ms = split.span_ms_per_round(tr, [4, 5])
    assert list(ms) == ["bsbodp.inputs", "sched", "algo"]
    inputs = [sp.host_dur for sp in tr.spans if sp.name == "bsbodp.inputs"]
    assert ms["bsbodp.inputs"] == pytest.approx(sum(inputs) / 2 * 1e3)
    assert ms["bsbodp.inputs"] <= ms["algo"]


def test_split_reads_the_shared_reductions():
    tr = Tracer()
    with tr.span("round 1", cat="round", round=1):
        with tr.span("execute", cat="execute"):
            with tr.span("bsbodp.student", cat="bsbodp"):
                time.sleep(1e-3)
    with tr.span("eval", cat="eval", round=1):
        pass
    ms = program_obs.span_ms_per_round(tr, [1])
    assert set(ms) == {"round", "execute", "bsbodp.student", "eval"}
    assert split.span_ms_per_round(tr, [1]) == {
        "bsbodp.student": ms["bsbodp.student"],
        "sched": pytest.approx(ms["round"] - ms["execute"]), "algo": ms["execute"]}
    r = split.split_profile([_host(), DEVICE])
    red = trace_reduce.reduce_profile([_host(), DEVICE])
    assert r["by_program"] == red["by_program"]
    assert (r["window_s"], r["busy_s"], r["launches"]) == (
        red["window_s"], red["busy_s"], red["launches"])


def test_counters_are_summed_over_their_labels():
    per = program_obs.per_round(
        {'fl_jit_calls_total{step="decode"}': 3.0},
        {'fl_jit_calls_total{step="decode"}': 9.0, 'fl_jit_calls_total{step="student"}': 6.0,
         'fl_stack_calls_total{step="stack"}': 2.0, 'fl_stack_calls_total{step="unstack"}': 2.0,
         "sim_other_total": 5.0}, 2)
    assert split.program_counters(per) == {"fl_jit_calls_total": 6.0,
                                           "fl_stack_calls_total": 2.0}


def test_a_window_reads_the_programs_counters_and_spans():
    cell = tiny_cell()
    st = harness.setup(cell, SEED, True, time.perf_counter())
    setup_stacks = sum(1 for sp in st["tracer"].spans
                       if sp.name in ("bsbodp.stack", "bsbodp.unstack"))
    out = split.measure(cell, st, 0.0, False)
    assert len(out["rounds"]) == 1 and "trace" not in out
    ms = out["spans"]["unprofiled"]
    assert {"bsbodp.direction", "bsbodp.inputs", "bsbodp.decode", "bsbodp.teacher",
            "bsbodp.student", "bsbodp.stack", "bsbodp.unstack"} <= set(ms)
    assert 0 < ms["bsbodp.direction"] <= ms["algo"]
    # three jitted calls a step of every direction the round ran, a
    # coalesced group's steps counted once
    steps, current = 0, None
    for sp in sorted(st["tracer"].spans, key=lambda s: s.t0_host):
        if sp.cat == "round":
            current = sp.args["round"]
        elif sp.name == "bsbodp.direction" and current in out["rounds"]:
            steps += sp.args["steps"]
    assert out["counters"]["fl_jit_calls_total"] == 3 * steps > 0
    assert out["counters"]["fl_h2d_bytes_total"] > 0
    # one stack and one unstack call a coalesced direction
    stacks = sum(1 for sp in st["tracer"].spans
                 if sp.name in ("bsbodp.stack", "bsbodp.unstack")) - setup_stacks
    assert out["counters"]["fl_stack_calls_total"] == stacks > 0


def test_the_command_needs_a_tpu(monkeypatch):
    monkeypatch.setattr(harness, "setup", lambda *a: pytest.fail("set up without a TPU"))
    assert split.main(["--workload", "c50e5.stable", "--seed", "1"]) == 2
