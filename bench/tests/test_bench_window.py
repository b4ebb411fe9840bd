"""The harness's whole run at a tiny size on the CPU, through
``harness.run_cell`` (the command itself refuses the CPU)."""
import time
from types import SimpleNamespace

import pytest
from bench_tiny import CPU, SEED, tiny_cell

from bench import harness, trace_reduce


@pytest.fixture(scope="module")
def run():
    return harness.run_cell(tiny_cell(), SEED, 0.5, False, time.perf_counter(), CPU)


def test_result_line_has_the_keys_in_order(run):
    assert list(run) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(run["metrics"]) == {"distill_samples_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in run["metrics"].values())
    assert run["device"]["platform"] == "cpu"


def test_window_runs_whole_rounds_of_every_pair(run):
    pairs = 4 + 2  # a pair per client and per edge
    assert run["attempted"] > 0 and run["attempted"] % pairs == 0
    assert run["failed"] == 0


def test_program_agrees_with_the_reference(run):
    assert run["correct"] is True
    assert set(run["checks"]) == set(tiny_cell().limits)
    for value, limit in run["checks"].values():
        assert value <= limit


def _with_a_device(reduce_profile):
    """The CPU's trace has no device plane: add one whose only launch, of
    ``jit_bsbodp_student``, runs inside the profiled round."""
    def reduce(planes, **kw):
        planes = list(planes)
        lo = next(e.start_ns for pl in planes if pl.name.startswith("/host")
                  for line in pl.lines for e in line.events if e.name == trace_reduce.WINDOW)
        line = lambda name, evs: SimpleNamespace(name=name, events=[
            SimpleNamespace(name=n, start_ns=a, duration_ns=d) for n, a, d in evs])
        device = SimpleNamespace(name="/device:TPU:0", lines=[
            line(trace_reduce.OPS_LINE, [("fusion", lo + 10, 100)]),
            line(trace_reduce.MODULES_LINE, [("jit_bsbodp_student(1)", lo + 5, 200)])])
        return reduce_profile(planes + [device], **kw)
    return reduce


@pytest.fixture(scope="module")
def traced():
    """A traced run's result and the ``record`` its metrics were read from."""
    records = []
    read = harness._read_metric

    def keep(name, record):
        records.append(record)
        return read(name, record)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace_reduce, "reduce_profile", _with_a_device(trace_reduce.reduce_profile))
        mp.setattr(harness, "_read_metric", keep)
        # the peaks table knows no CPU: the made-up device plane stands for a v5e
        out = harness.run_cell(tiny_cell(), SEED, 0.5, True, time.perf_counter(),
                               dict(CPU, kind="TPU v5 lite"))
    return out, records[0]


def test_a_traced_run_reads_the_programs_spans_counters_and_programs(traced):
    out, _ = traced
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("student_host_ms_per_round", "unstack_host_ms_per_round",
                 "inputs_host_ms_per_round"):
        assert 0 < m[name] < m["bsbodp_host_ms_per_round"]
    # three jitted calls (decode, teacher, student) a distill step, in every
    # round alike
    calls = m["jit_step_calls_per_round"]
    assert calls > 0 and calls % 3 == 0
    assert m["student_device_ms_per_round"] == pytest.approx(1e-4)
    assert out["correct"] is True


def test_the_profiled_rounds_work_by_model_and_step_sums_to_its_operations(traced):
    trace = traced[1]["trace"]
    work = trace["work"]
    assert set(work) == {"decoder", "tiny_cnn", "tiny_resnet_a", "tiny_resnet_b"}
    assert set(work["tiny_resnet_b"]) == {"teacher", "student", "eval"}
    assert set(work["tiny_cnn"]) == {"teacher", "student_leaf"}
    assert all(w["flops"] > 0 for m in work.values() for w in m.values())
    assert {(m, k) for m, ws in work.items() for k, w in ws.items() if not w["bytes"]} == {
        ("decoder", "decode")}
    # the round's operations as the harness counted them before it kept
    # them by model and step
    assert trace["ops"] == sum(w["flops"] for m in work.values() for w in m.values()) == 64_578_560
