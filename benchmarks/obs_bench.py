"""Observability regression file: the telemetry plane's stability contract.

``collect()`` runs a small traced ``straggler_heavy`` simulation and one
eval, and records

* the **metric names** registered by the sim engine and the global
  registry (the dashboards-don't-break contract),
* the **span categories** the tracer emitted,
* the **critical-path gate** of round 0 (node + factor — deterministic,
  a pure function of scenario + seed).

Everything lands in the tracked ``BENCH_obs.json`` at the repo root.
``check()`` recomputes and diffs — that's the ``benchmarks.run
--check-obs`` CI gate. Counts/durations are never compared, only names,
categories, and the gate attribution.
"""
from __future__ import annotations

import os

BENCH_PATH = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_obs.json")
)


def _traced_run(rounds: int = 1, clients: int = 4, edges: int = 2):
    """One traced straggler_heavy FedEEC run; returns (tracer, engine)."""
    from repro.configs.fedeec_paper import paper_setting
    from repro.fl.api import create_algorithm
    from repro.fl.engine import build_problem
    from repro.obs.trace import Tracer
    from repro.sim.engine import SimEngine
    from repro.sim.scenarios import get_scenario

    cfg = paper_setting(
        "synth_cifar10", clients, edges, samples_per_client=16,
        test_samples=64, image_size=8, embed_dim=16,
        edge_model="cnn2", cloud_model="cnn2",
    )
    _, tree, client_data, auto = build_problem(cfg)
    trainer = create_algorithm("fedeec", cfg, tree, client_data, auto)
    tracer = Tracer()
    engine = SimEngine(trainer, get_scenario("straggler_heavy"),
                       seed=cfg.seed, tracer=tracer)
    engine.run(rounds)
    return tracer, engine


def collect() -> dict:
    import jax.numpy as jnp

    from repro.obs.critical_path import rounds_from_eventlog
    from repro.obs.metrics import global_registry

    tracer, engine = _traced_run()

    # one eval so the fl_* family (eval wall time) is in the contract too
    from repro.fl.metrics import accuracy

    accuracy(lambda p, xb: xb @ p, jnp.eye(4), jnp.eye(4), [0, 1, 2, 3])

    reports = rounds_from_eventlog(engine.log.entries)
    gate = reports[0] if reports else None
    return {
        "sim_metric_names": engine.metrics.names(),
        "global_metric_names": global_registry().names(),
        "span_categories": sorted({sp.cat for sp in tracer.spans if sp.cat}),
        "round0_gate": {
            "node": gate.gate_node if gate else "",
            "factor": gate.gate_factor if gate else "",
        },
    }


def write_bench(path: str = BENCH_PATH) -> dict:
    from benchmarks import gate

    return gate.write_tracked(path, collect())


def check_bench(path: str = BENCH_PATH) -> int:
    """The --check-obs gate: metric names, span categories, and the
    round-0 gate attribution must match the tracked file exactly."""
    from benchmarks import gate

    tracked = gate.load_tracked(path, "--update-obs")
    if tracked is None:
        return 2
    problems = gate.diff_keys(tracked, collect(),
                              ("sim_metric_names", "global_metric_names",
                               "span_categories", "round0_gate"))
    return gate.report(
        "obs bench", problems,
        f"metric names, span categories, and gate attribution match {path}",
        "--update-obs")
