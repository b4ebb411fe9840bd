"""The program's own spans and counters, reduced per measured round.

Spans come from the program's tracer (``repro.obs.trace.Tracer``), which
the harness installs in a traced run; counters from the program's global
registry (``repro.obs.metrics.global_registry``), which is always on. Both
``bench/harness.py`` (a ``--trace 1`` run's ``record``) and
``bench/split.py`` read them through these functions.
"""
from __future__ import annotations

import statistics
from collections import defaultdict


def _ms_per_round(tracer, rounds, key) -> dict[str, float]:
    """Host milliseconds per round over ``rounds`` of the tracer's spans,
    summed by ``key(span)``. A span belongs to the round whose span began
    last before it, so a round's eval counts in that round."""
    by_round: dict[int, dict] = {r: defaultdict(float) for r in rounds}
    current = None
    for sp in sorted(tracer.spans, key=lambda s: s.t0_host):
        if sp.cat == "round":
            current = sp.args.get("round")
        if current in by_round:
            by_round[current][key(sp)] += sp.host_dur
    names = set().union(*by_round.values())
    return {k: statistics.mean(v[k] for v in by_round.values()) * 1e3 for k in sorted(names)}


def span_ms_per_round(tracer, rounds) -> dict[str, float]:
    """Host milliseconds per round of every span the tracer recorded in
    ``rounds``, by name; the round spans themselves (``round <r>``) under
    ``round``."""
    return _ms_per_round(tracer, rounds, lambda sp: "round" if sp.cat == "round" else sp.name)


def sched_algo_ms(tracer, rounds) -> tuple[float, float]:
    """(scheduler, algorithm) host milliseconds per round: the round span
    minus its execute spans, and the execute spans."""
    by_cat = _ms_per_round(tracer, rounds, lambda sp: sp.cat)
    algo = by_cat.get("execute", 0.0)
    return by_cat["round"] - algo, algo


def counters() -> dict[str, float]:
    """Every counter of the program's global registry, by its series key
    with its labels (``fl_jit_calls_total{step="student"}``)."""
    from repro.obs.metrics import global_registry

    return {k: m["value"] for k, m in global_registry().snapshot().items()
            if m["type"] == "counter"}


def per_round(before: dict, after: dict, rounds: int) -> dict[str, float]:
    """Each counter's change from ``before`` to ``after``, per round."""
    return {k: (v - before.get(k, 0.0)) / rounds for k, v in after.items()}
