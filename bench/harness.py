"""One run of one cell: set-up, warm-up, the measured window, the traced
round and the check against the plain reference.

The window drives the program's normal path: ``build_problem`` ->
``create_algorithm("fedeec", ...)`` -> ``SimEngine(trainer, scenario, seed)``
-> ``engine.run(r + 1, eval_fn=...)`` once per round, which ends in
``FedEEC.execute`` / ``execute_batch`` and the cloud eval on the test split.
"""
from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from bench import check, flops, program_obs, spec
from bench.reference import PROBE_STEPS
from bench.spec import Cell

# the program's own eval batch (repro.fl.metrics.accuracy)
EVAL_BATCH = 256


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """JAX's ``backend_compile_duration`` events, with the host time at
    which each was reported."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def between(self, t0: float, t1: float) -> list[float]:
        return [s for t, s in self.events if t0 <= t < t1]


def register_models(config: dict) -> None:
    """Register the configuration's tier models with the program's model
    registry, each built by its family's ``register``
    (``bench/families/<family>.py``) at the widths the file gives. Names
    already registered are the program's own."""
    from repro.models.registry import FL_MODELS

    for name, m in config["models"].items():
        if name not in FL_MODELS:
            spec.family(m["family"]).register(name, m, FL_MODELS)


class Evaluator:
    """The round's eval: the cloud model's logits on the test split in
    batches of 256, read back to the host; returns the accuracy."""

    def __init__(self, trainer, x_test, y_test):
        self.trainer, self.x, self.y = trainer, x_test, y_test
        self.fn = jax.jit(trainer.cloud_apply())
        self.times: list[float] = []
        self.logits = None

    def __call__(self) -> float:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.eval"):
            self.logits = self.logits_of(self.trainer.cloud_params())
        self.times.append(time.perf_counter() - t0)
        return float(np.mean(np.argmax(self.logits, -1) == self.y))

    def logits_of(self, params) -> np.ndarray:
        import jax.numpy as jnp

        return np.concatenate([np.asarray(self.fn(params, jnp.asarray(self.x[i:i + EVAL_BATCH])))
                               for i in range(0, len(self.y), EVAL_BATCH)])


class Probe:
    """Records the dispatch groups of the first round through the
    trainer's public ``execute``/``execute_batch``, each node's state after
    the first group it took part in, and the first ``STEPS`` student steps
    of each node's first direction as student: what the program's jitted
    student step returns (the loss, the optimizer state after the first
    step, the parameters after the last). Each snapshot is reduced to its
    norms against ``init`` (a host copy of the parameters before the round)
    when it is taken, so the probe holds no device array of a node's state;
    ``check_s`` is the host time spent reducing. Installed for the checked
    round only; the window runs the trainer's own methods."""

    STEPS = PROBE_STEPS

    def __init__(self, trainer, init: dict):
        self.t, self.init = trainer, init
        self.groups: list[tuple[list[tuple[str, str]], int]] = []
        self.first: dict = {}
        # node -> {"loss": [floats], "v1": v_norms, "p3": change_norms}
        self.steps: dict = {}
        self.check_s = 0.0
        # (b, node) of this direction's fresh students; b indexes a
        # coalesced group's stacked outputs, None for a serial step
        self._fresh: list[tuple[int, str]] = []
        self._calls = self._last = 0
        self._depth = 0

    def _direction(self, orig):
        def run(arg, *rest):
            stacked = isinstance(arg, list)  # a coalesced group's pairs
            pairs = arg if stacked else [(arg, *rest)]
            self._fresh = [(b if stacked else None, v_s)
                           for b, (v_s, _) in enumerate(pairs) if v_s not in self.steps]
            for _, v in self._fresh:
                self.steps[v] = {"loss": []}
            self._calls = 0
            self._last = min(self.STEPS, self.t.pair_steps(*pairs[0]))
            try:
                orig(arg, *rest)
            finally:
                self._fresh = []
        return run

    def _student(self, orig):
        def get(*args):
            fn = orig(*args)

            def step(*a):
                out = fn(*a)
                self._calls += 1
                if self._calls <= self._last and self._fresh:
                    jax.block_until_ready(out)  # the step's own time is not the check's
                    t0 = time.perf_counter()
                    params, opt, loss = out
                    for b, v in self._fresh:
                        rec = self.steps[v]
                        rec["loss"].append(check.pick(loss, b).item())
                        if self._calls == 1:
                            rec["v1"] = check.v_norms(opt["v"], b)
                        if self._calls == self._last:
                            rec["p3"] = check.change_norms(params, self.init[v], b)
                    self.check_s += time.perf_counter() - t0
                return out
            return step
        return get

    def step_records(self) -> dict:
        """node -> (losses, ``v_norms`` after the first step, ``change_norms``
        after the last recorded step)."""
        return {v: (r["loss"], r["v1"], r["p3"]) for v, r in self.steps.items()}

    def _wrap(self, orig):
        def run(arg):
            items = arg if isinstance(arg, list) else [arg]
            top = self._depth == 0
            if top:
                self.groups.append(([(it.node, it.peer) for it in items],
                                    items[0].steps))
            self._depth += 1
            try:
                orig(arg)
            finally:
                self._depth -= 1
            if top:
                fresh = [v for it in items for v in (it.node, it.peer) if v not in self.first]
                jax.block_until_ready([self.t.params[v] for v in fresh])
                t0 = time.perf_counter()
                for v in fresh:
                    self.first[v] = check.change_norms(self.t.params[v], self.init[v])
                self.check_s += time.perf_counter() - t0
        return run

    WRAPPED = ("execute", "execute_batch", "_bsbodp_directional",
               "_bsbodp_directional_batched", "_student_fn", "_student_fn_batched")

    def __enter__(self):
        t = self.t
        t.execute = self._wrap(t.execute)
        t.execute_batch = self._wrap(t.execute_batch)
        t._bsbodp_directional = self._direction(t._bsbodp_directional)
        t._bsbodp_directional_batched = self._direction(t._bsbodp_directional_batched)
        t._student_fn = self._student(t._student_fn)
        t._student_fn_batched = self._student(t._student_fn_batched)
        return self

    def __exit__(self, *exc):
        for name in self.WRAPPED:
            delattr(self.t, name)


def _round_work(entries, trainer, cfg, config):
    """Pair items of one round from the program's event log: (attempted,
    executed, distilled samples, the distillation's operations and least
    HBM bytes by model and step, ``work[model][step]``)."""
    attempted = sum(1 for e in entries if e["kind"] in ("pair_start", "pair_skip"))
    done = [(e["node"], e["target"]) for e in entries if e["kind"] == "pair_done"]
    samples, work = 0, {}
    for a, b in done:
        child = a if trainer.tree.parent.get(a) == b else b
        bs = min(cfg.batch_size, len(trainer.embeddings[child][1]))
        steps = trainer.pair_steps(a, b)
        samples += 2 * steps * bs
        for vs, vt in ((a, b), (b, a)):
            local = 0
            if vs in trainer.client_data:
                local = min(bs, len(trainer.client_data[vs][1]))
            for key, (f, nbytes) in flops.direction_work(
                    config, steps, bs, trainer.model_of[vt], trainer.model_of[vs],
                    local).items():
                flops.add_work(work, key, f, nbytes)
    return attempted, len(done), samples, work


def _device_memory_peak() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def _profile_round(run_round):
    """Run one round under ``jax.profiler`` with the Python tracer off.
    Returns the round's host start and end, the ``bench.clock`` marker's
    host time, the trace's path and its temporary directory."""
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # user annotations only
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.clock"):
            marker = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            ta = time.perf_counter()
            run_round()
            tb = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    return ta, tb, {"path": path, "tmp": tmp, "marker": marker}


# host label of each lived span category of the program's tracer
HOST_LABELS = {"round": "scheduler", "churn": "churn", "dispatch": "dispatch_group",
               "eval": "eval"}


def _host_spans(tracer, origin):
    return [(HOST_LABELS.get(sp.cat, sp.name), origin + sp.t0_host, origin + sp.t1_host)
            for sp in tracer.spans if sp.cat in HOST_LABELS or sp.cat == "execute"]


def make_problem(cell: Cell, seed: int):
    """The benchmark's inputs for this seed (``bench.reference``): the
    clients' images and labels, the test split, and the pre-trained bridge
    autoencoder."""
    from bench import reference

    fl = cell.config["fl_config"]
    inputs = reference.make_inputs(cell.config, seed)
    auto = reference.pretrain_autoencoder(inputs.x_open, fl["image_size"], fl["embed_dim"],
                                          seed)
    return inputs, auto


def setup(cell: Cell, seed: int, trace: bool, t_start: float):
    """Everything before the first measured round: the inputs, the trainer,
    the first round with its eval, and further warm-up rounds. Returns the
    live state and the program's side of the check. ``setup_s`` leaves out
    the benchmark's own work: making the inputs (``make_problem``), the eval
    of the cloud's initial parameters that only the check reads, and reducing
    the program's side of the check."""
    from repro.configs.base import FLConfig
    from repro.core.topology import Tree
    from repro.fl.api import create_algorithm
    from repro.obs.trace import Tracer
    from repro.sim.engine import SimEngine
    from repro.sim.scenarios import get_scenario

    st = {"compiles": CompileLog()}
    register_models(cell.config)
    cfg = FLConfig(**cell.config["fl_config"], seed=seed)
    t0 = time.perf_counter()
    inputs, auto = make_problem(cell, seed)
    bench_s = time.perf_counter() - t0
    tree = Tree.three_tier(cfg.num_edges, cfg.num_clients)
    client_data = {f"client{i}": (inputs.x_train[p], inputs.y_train[p])
                   for i, p in enumerate(inputs.parts)}
    t0 = time.perf_counter()
    trainer = create_algorithm("fedeec", cfg, tree, client_data, auto)
    jax.block_until_ready(trainer.params)
    st["trainer_init_s"] = time.perf_counter() - t0
    sc = get_scenario(cell.traffic["scenario"]).with_overrides(
        **cell.traffic.get("overrides", {}))
    tracer, origin = None, None
    if trace:
        origin = time.perf_counter()
        tracer = Tracer()
    engine = SimEngine(trainer, sc, seed=cfg.seed, tracer=tracer)
    evaluator = Evaluator(trainer, inputs.x_test, inputs.y_test)
    t0 = time.perf_counter()
    init = check.to_host(trainer.params)
    init_s = time.perf_counter() - t0
    with Probe(trainer, init) as probe:
        engine.run(1, eval_fn=evaluator)
    logits_round = evaluator.logits
    # the round's compiled eval, on the cloud's parameters before the round
    t0 = time.perf_counter()
    logits_init = evaluator.logits_of(jax.device_put(init[trainer.tree.root]))
    bench_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    end = check.end_norms(trainer.params, trainer.opt, init)
    reduce_s = init_s + probe.check_s + time.perf_counter() - t0
    for r in range(1, cell.traffic["warmup_rounds"]):
        engine.run(r + 1, eval_fn=evaluator)
    jax.block_until_ready(trainer.params)
    t_chk = time.perf_counter()
    side = check.summarize(init, probe.first, end,
                           {v: trainer.embeddings[v][0] for v in trainer.client_data},
                           logits_init, logits_round, steps=probe.step_records())
    del init, end, probe.first, probe.steps
    t_setup_end = time.perf_counter()
    evaluator.times.clear()
    st.update(cfg=cfg, trainer=trainer, engine=engine, evaluator=evaluator,
              tracer=tracer, origin=origin, groups=probe.groups, side=side,
              inputs=inputs, auto=auto, check_s=t_setup_end - t_chk + reduce_s,
              bench_s=bench_s, setup_s=t_chk - t_start - bench_s - reduce_s,
              setup_window=(t_start, t_setup_end), next_round=cell.traffic["warmup_rounds"])
    return st


def window(cell: Cell, st: dict, seconds: float, trace: bool) -> dict:
    """Whole rounds until the next one would end after ``seconds``; at least
    one. In a traced run the first measured round runs under the profiler."""
    engine, trainer, evaluator = st["engine"], st["trainer"], st["evaluator"]
    dispatches = engine.metrics.counter("sim_dispatches_total")
    rounds, r = [], st["next_round"]
    every = cell.traffic["eval_every"]
    profiled = None
    t_w0 = time.perf_counter()
    d0 = dispatches.value
    while not rounds or (time.perf_counter() - t_w0) + rounds[-1]["dur"] <= seconds:
        n0 = len(engine.log.entries)

        def run_round(r=r):
            engine.run(r + 1, eval_fn=evaluator if (r + 1) % every == 0 else None)

        if trace and profiled is None:
            ta, tb, profiled = _profile_round(run_round)
            profiled["round"] = r
        else:
            ta = time.perf_counter()
            run_round()
            tb = time.perf_counter()
        att, done, samples, work = _round_work(engine.log.entries[n0:], trainer,
                                               st["cfg"], cell.config)
        rounds.append({"round": r, "t0": ta, "t1": tb, "dur": tb - ta, "attempted": att,
                       "executed": done, "samples": samples, "work": work})
        r += 1
    t_w1 = time.perf_counter()
    return {"rounds": rounds, "t0": t_w0, "t1": t_w1, "profiled": profiled,
            "dispatches": dispatches.value - d0, "eval_s": list(evaluator.times)}


def _reduce_trace(cell: Cell, st: dict, w: dict) -> dict:
    from bench import trace_reduce

    prof = w["profiled"]
    spans = _host_spans(st["tracer"], st["origin"])
    try:
        red = trace_reduce.reduce_file(prof["path"], marker_perf=prof["marker"],
                                       host_spans=spans)
    finally:
        shutil.rmtree(prof["tmp"], ignore_errors=True)
    rnd = next(x for x in w["rounds"] if x["round"] == prof["round"])
    fl = cell.config["fl_config"]
    work = rnd["work"]
    flops.add_work(work, (fl["cloud_model"], "eval"), *flops.eval_work(cell.config, EVAL_BATCH))
    red["work"] = work
    red["ops"] = sum(w["flops"] for steps in work.values() for w in steps.values())
    red["rounds"] = 1
    return red


def run_reference(cell: Cell, seed: int, groups, inputs, auto, dtype=None,
                  fault: str = ""):
    """The reference's side of the check on the same inputs."""
    import jax.numpy as jnp

    from bench.reference import Reference

    ref = Reference(cell.config, seed, inputs, auto, dtype=dtype or jnp.float32,
                    fault=fault)
    init = check.to_host(ref.params)
    logits_init = ref.logits(ref.params[ref.root])
    first, grad_norms, steps = ref.replay(groups, init)
    end = check.end_norms(ref.params, ref.opt, init)
    logits_round = ref.logits(ref.params[ref.root])
    return check.summarize(init, first, end, {v: ref.stores[v][0] for v in ref.data},
                           logits_init, logits_round, steps=steps, grad_norms=grad_norms,
                           group0=[v for pair in groups[0][0] for v in pair])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             device: dict) -> dict:
    """One run; returns the result line's object."""
    st = setup(cell, seed, trace, t_start)
    log(f"[bench] set-up {st['setup_s']:.3f}s (trainer init "
        f"{st['trainer_init_s']:.3f}s); not counted: the benchmark's inputs and "
        f"the eval of the initial cloud {st['bench_s']:.3f}s, the program's side "
        f"of the check {st['check_s']:.3f}s")
    c0 = program_obs.counters() if trace else None
    w = window(cell, st, seconds, trace)
    c1 = program_obs.counters() if trace else None
    rounds = w["rounds"]
    span = rounds[-1]["t1"] - rounds[0]["t0"]
    for x in rounds:
        log(f"[bench] round {x['round']}: {x['dur']:.3f}s, {x['executed']} pairs, "
            f"{x['samples']} samples")
    device = dict(device, memory_peak_bytes=_device_memory_peak())
    record = {
        "trainer_init_s": st["trainer_init_s"],
        "setup_compile_s": sum(st["compiles"].between(*st["setup_window"])),
        "compiles_in_window": len(st["compiles"].between(w["t0"], w["t1"])),
        "rounds": rounds, "eval_s": w["eval_s"],
        "dispatches_per_round": w["dispatches"] / len(rounds),
        "setup_s": st["setup_s"], "window_s": span,
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    breakdown = None
    if trace:
        red = _reduce_trace(cell, st, w)
        record["trace"] = red
        ids = [x["round"] for x in rounds]
        record["sched_ms"], record["algo_ms"] = program_obs.sched_algo_ms(st["tracer"], ids)
        record["span_ms"] = program_obs.span_ms_per_round(st["tracer"], ids)
        record["counters"] = program_obs.per_round(c0, c1, len(rounds))
        record["peaks"] = spec.peaks(device["kind"])
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = _read_metric(m["name"], record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = sum(x["attempted"] for x in rounds)
    failed = attempted - sum(x["executed"] for x in rounds)
    groups, side, inputs, auto = st["groups"], st["side"], st["inputs"], st["auto"]
    del st, w
    gc.collect()
    t_ref = time.perf_counter()
    ref = run_reference(cell, seed, groups, inputs, auto)
    log(f"[check] reference {time.perf_counter() - t_ref:.3f}s")
    values = check.readings(side, ref)
    correct, compared = check.verdict(values, cell.limits)
    for k, v in sorted(values.items()):
        lim = cell.limits.get(k)
        log(f"[check] {k} {v:.6e}" + (f" limit {lim:.6e}" if lim is not None
                                      else " (not compared)"))
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = compared
    return out


def _read_metric(name: str, record: dict):
    if name == "distill_samples_per_s":
        return sum(x["samples"] for x in record["rounds"]) / record["window_s"]
    if name == "setup_s":
        return record["setup_s"]
    return spec.metric_reader(name)(record)
