"""FedEEC training on one TPU chip, end to end, through the normal entry point.

    python chip_smoke.py

Drives ``repro.fl.engine.run_experiment`` (the call ``python -m
repro.sim.runner`` makes) at the paper's CIFAR-10 setting: 50 clients over
5 edges, 32x32 images, the ``cnn1`` -> ``resnet10`` -> ``resnet18`` tier
ladder at the registry's widths, batch 8, random weights from seed 0.

  A  fedeec on ``stable``, 3 rounds, an eval every round: set-up seconds,
     seconds per round, compile counts, accuracy curve, dispatch counters.
  B  the same run again in this process (tracer off, so the untraced
     dispatch loop runs): its event signature must equal A's.
  C  fedeec on ``flash_crowd``, 2 rounds: coalesced ``jit(vmap)`` pair
     dispatches and at least one migration must happen.
  D  one BSBODP direction of the cloud pair and one of a leaf pair (a
     teacher call plus a student step, the trainer's own jitted functions)
     at the default matmul precision against ``"highest"``.

Every phase checks that accuracies lie in [0, 1], that the cloud model's
test loss and every node's parameters and optimizer state are finite (a
non-finite distillation loss would have made them so), and that the cloud
parameters moved. Any failed check exits non-zero. The script needs a TPU:
on any other platform it exits non-zero before doing anything. Its last
line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ALGORITHM = "fedeec"
PROBE = "fedeec-chip-smoke"  # registry alias that keeps a handle on the trainer
# phase D limits (CHANGES.md gives the reasons)
PROBS_TOL = 5e-2  # teacher probabilities, default vs highest precision
LOSS_RTOL = 5e-2  # student loss, relative


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


# -- compile accounting -------------------------------------------------------

COMPILES: list[tuple[float, float]] = []  # (perf_counter at the end, seconds)
CACHE_HITS: list[float] = []
TRAINERS: list = []  # (trainer, cloud params at construction), one per run


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES.append((time.perf_counter(), secs))


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        CACHE_HITS.append(time.perf_counter())


def compiles_between(t0: float, t1: float) -> tuple[int, float]:
    secs = [s for t, s in COMPILES if t0 <= t < t1]
    return len(secs), sum(secs)


def fmt_compiles(t0: float, t1: float) -> str:
    n, s = compiles_between(t0, t1)
    hits = sum(1 for t in CACHE_HITS if t0 <= t < t1)
    return f"{n} compiles, {s:.3f}s (persistent-cache hits {hits})"


# -- checks -------------------------------------------------------------------


def tree_finite(tree) -> bool:
    import jax
    import numpy as np

    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree_util.tree_leaves(tree))


def max_abs_diff(a, b) -> float:
    import jax
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def cloud_test_loss(trainer, ds, batch: int = 250) -> float:
    import jax
    import jax.numpy as jnp

    apply = trainer.cloud_apply()

    @jax.jit
    def ce(p, x, y):
        logp = jax.nn.log_softmax(apply(p, x), axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()

    total = sum(float(ce(trainer.cloud_params(), jnp.asarray(ds.x_test[i:i + batch]),
                         jnp.asarray(ds.y_test[i:i + batch])))
                for i in range(0, len(ds.y_test), batch))
    return total / len(ds.y_test)


def check_phase(name: str, res, trainer, cloud0, ds, rounds: int) -> None:
    check(len(res.acc_curve) == rounds,
          f"{name}: {len(res.acc_curve)} evals for {rounds} rounds")
    check(all(0.0 <= a <= 1.0 for a in res.acc_curve),
          f"{name}: accuracy outside [0, 1]: {res.acc_curve}")
    check(tree_finite(trainer.params) and tree_finite(trainer.opt),
          f"{name}: non-finite parameters or optimizer state")
    loss = cloud_test_loss(trainer, ds)
    print(f"[{name}] cloud test loss {loss:.6f}")
    check(loss == loss and abs(loss) != float("inf"),
          f"{name}: non-finite cloud test loss {loss}")
    moved = max_abs_diff(trainer.cloud_params(), cloud0)
    print(f"[{name}] cloud params max |change| {moved:.6e}")
    check(moved > 0.0, f"{name}: cloud params did not change")


# -- phases -------------------------------------------------------------------


def run_phase(name: str, cfg, scenario: str, rounds: int, *, traced: bool):
    """One ``run_experiment`` call; returns (result, trainer, cloud params
    at construction, tracer or None, perf_counter at the call)."""
    import jax

    from repro.fl.engine import run_experiment
    from repro.obs.trace import Tracer

    TRAINERS.clear()
    tracer = Tracer() if traced else None
    t_call = time.perf_counter()
    res = run_experiment(PROBE, cfg, rounds=rounds, eval_every=1,
                         scenario=scenario, tracer=tracer)
    check(len(TRAINERS) == 1, f"{name}: trainer was not built once")
    trainer, cloud0 = TRAINERS.pop()
    jax.block_until_ready(trainer.cloud_params())
    t_end = time.perf_counter()
    print(f"[{name}] {scenario}, {rounds} rounds: {t_end - t_call:.3f}s in "
          f"run_experiment; {fmt_compiles(t_call, t_end)}")
    print(f"[{name}] accuracy per round {[round(a, 6) for a in res.acc_curve]}")
    print(f"[{name}] dispatch_stats {res.dispatch_stats}")
    print(f"[{name}] event counts {res.event_counts}")
    print(f"[{name}] event signature {res.event_signature}")
    return res, trainer, cloud0, tracer, t_call


def report_rounds(name: str, tracer, t_call: float) -> None:
    """Set-up and per-round seconds from the tracer's host clock. A round
    runs from the start of its ``round`` span to the end of its ``eval``
    span: the eval reads the cloud model's predictions back to the host,
    so it waits for every update of the cloud params."""
    rounds = sorted((s for s in tracer.spans if s.cat == "round"),
                    key=lambda s: s.t0_host)
    evals = {s.args["round"]: s for s in tracer.spans if s.cat == "eval"}
    check(len(rounds) > 0, f"{name}: no round spans")
    setup = rounds[0].t0_host
    print(f"[{name}] set-up {setup:.3f}s (problem build, autoencoder "
          f"pre-training, trainer init); "
          f"{fmt_compiles(t_call, t_call + setup)}")
    for sp in rounds:
        r = sp.args["round"]
        ev = evals[r]
        t0, t1 = t_call + sp.t0_host, t_call + ev.t1_host
        print(f"[{name}] round {r}: {t1 - t0:.3f}s (eval {ev.host_dur:.3f}s); "
              f"{fmt_compiles(t0, t1)}")
    first, last = t_call + rounds[0].t0_host, t_call + evals[
        rounds[-1].args["round"]].t1_host
    print(f"[{name}] rounds total: {fmt_compiles(first, last)}")


def bsbodp_direction(trainer, v_s: str, v_t: str):
    """One distill step of the direction v_t teaches v_s, with the
    trainer's jitted decode, teacher and student functions. Index draws
    come from a fixed generator so both precisions see the same batch."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    child = v_s if trainer.tree.parent.get(v_s) == v_t else v_t
    eps, labels = trainer.embeddings[child]
    bs = min(trainer.cfg.batch_size, len(labels))
    idx = rng.choice(len(labels), size=bs, replace=len(labels) < bs)
    bridge = trainer._decode_fn()(jnp.asarray(eps[idx]))
    y = jnp.asarray(labels[idx])
    probs, q, _ = trainer._teacher_fn(trainer.model_of[v_t])(
        trainer.params[v_t], trainer.skr[v_t], bridge, y)
    leaf = v_s in trainer.client_data
    args = [trainer.params[v_s], trainer.opt[v_s], bridge, y, q]
    if leaf:
        lx, ly = trainer.client_data[v_s]
        li = rng.choice(len(ly), size=min(bs, len(ly)), replace=len(ly) < bs)
        args += [jnp.asarray(lx[li]), jnp.asarray(ly[li])]
    params, _, loss = trainer._student_fn(trainer.model_of[v_s], leaf)(*args)
    return probs, params, float(loss)


def phase_d(cfg, tree, client_data, auto, probs_tol: float,
            loss_rtol: float) -> None:
    import jax

    from repro.fl.api import create_algorithm

    t0 = time.perf_counter()
    trainer = create_algorithm(ALGORITHM, cfg, tree, client_data, auto)
    edge = trainer.tree.children[trainer.tree.root][0]
    leaf = next(c for c in trainer.tree.children[edge]
                if c in trainer.client_data)
    # a first Adam step moves each parameter by at most lr, so two runs
    # of it can differ by at most 2 lr in any parameter
    params_tol = 2.0 * cfg.lr * (1.0 + 1e-3)
    for label, v_s, v_t in (("cloud pair", trainer.tree.root, edge),
                            ("leaf pair", leaf, edge)):
        p_def, w_def, l_def = bsbodp_direction(trainer, v_s, v_t)
        with jax.default_matmul_precision("highest"):
            p_ref, w_ref, l_ref = bsbodp_direction(trainer, v_s, v_t)
        dp = max_abs_diff(p_def, p_ref)
        dw = max_abs_diff(w_def, w_ref)
        dl = abs(l_def - l_ref) / max(abs(l_ref), 1e-12)
        print(f"[D] {label} ({trainer.model_of[v_t]} teaches "
              f"{trainer.model_of[v_s]}): teacher probs max |diff| {dp:.6e} "
              f"(tol {probs_tol:g}); updated params max |diff| {dw:.6e} "
              f"(tol {params_tol:g}); student loss {l_def:.6f} vs {l_ref:.6f}, "
              f"rel diff {dl:.6e} (tol {loss_rtol:g})")
        check(tree_finite((p_def, w_def, l_def)) and tree_finite((p_ref, w_ref, l_ref)),
              f"D {label}: non-finite teacher probs, params or loss")
        check(dp <= probs_tol, f"D {label}: teacher probs differ by {dp}")
        check(dw <= params_tol, f"D {label}: updated params differ by {dw}")
        check(dl <= loss_rtol, f"D {label}: student loss differs by {dl}")
    t1 = time.perf_counter()
    print(f"[D] {t1 - t0:.3f}s; {fmt_compiles(t0, t1)}")


def run(cfg, *, probs_tol: float = PROBS_TOL, loss_rtol: float = LOSS_RTOL,
        rounds_a: int = 3, rounds_c: int = 2) -> None:
    """Phases A-D on whatever device JAX has; raises ``Failed``."""
    import jax

    from repro.fl.api import ALGORITHM_REGISTRY, create_algorithm, register_algorithm
    from repro.fl.engine import build_problem

    if PROBE not in ALGORITHM_REGISTRY:
        @register_algorithm(PROBE)
        def _probe(cfg, tree, client_data, auto):
            # the trainer is the registry's own fedeec; steps never donate
            # buffers, so the initial cloud params stay readable
            t = create_algorithm(ALGORITHM, cfg, tree, client_data, auto)
            TRAINERS.append((t, t.cloud_params()))
            return t

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    t_start = time.perf_counter()

    res_a, tr_a, c0_a, tracer, t_call = run_phase("A", cfg, "stable", rounds_a,
                                                   traced=True)
    report_rounds("A", tracer, t_call)
    ds, tree, client_data, auto = build_problem(cfg)
    check_phase("A", res_a, tr_a, c0_a, ds, rounds_a)
    del tr_a, c0_a, tracer
    print("[A] passed")

    res_b, tr_b, c0_b, _, _ = run_phase("B", cfg, "stable", rounds_a,
                                        traced=False)
    check_phase("B", res_b, tr_b, c0_b, ds, rounds_a)
    same = res_b.event_signature == res_a.event_signature
    print(f"[B] replay signature {res_b.event_signature} "
          f"{'==' if same else '!='} phase A {res_a.event_signature}")
    check(same, "B: replay signature differs from phase A")
    del tr_b, c0_b
    print("[B] passed")

    res_c, tr_c, c0_c, _, _ = run_phase("C", cfg, "flash_crowd", rounds_c,
                                        traced=False)
    check_phase("C", res_c, tr_c, c0_c, ds, rounds_c)
    batched = res_c.dispatch_stats["batched_dispatches"]
    migrations = res_c.event_counts.get("migrate", 0)
    print(f"[C] batched_dispatches {batched}, migrations {migrations}")
    check(batched > 0, "C: no coalesced pair dispatch")
    check(migrations > 0, "C: no migration")
    del tr_c, c0_c
    print("[C] passed")

    phase_d(cfg, tree, client_data, auto, probs_tol, loss_rtol)
    print("[D] passed")
    t_end = time.perf_counter()
    print(f"[all] {t_end - t_start:.3f}s; {fmt_compiles(t_start, t_end)}")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.configs.fedeec_paper import paper_setting
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}; "
          f"jax {jax.__version__}")
    print(f"compilation cache: {enable_compile_cache()}")
    cfg = paper_setting("synth_cifar10", 50, 5, image_size=32,
                        samples_per_client=200, test_samples=1000)
    print(f"config: {cfg.num_clients} clients / {cfg.num_edges} edges, "
          f"{cfg.image_size}x{cfg.image_size}, {cfg.end_model} -> "
          f"{cfg.edge_model} -> {cfg.cloud_model}, batch {cfg.batch_size}, "
          f"max_distill_steps {cfg.max_distill_steps}, seed {cfg.seed}")
    try:
        run(cfg)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
