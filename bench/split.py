"""The split of a cell's round by the program's own spans, named jitted
steps and counters, on the TPU it is started on.

    python3 bench/split.py --workload c50e5.stable --seed 7 --seconds 90

It runs the cell's set-up as ``bench/run.py`` does, then whole rounds for
``--seconds`` with the first under the profiler, and prints, per round:

* ``counters``: the deltas of the program's always-on counters
  (``PROGRAM_COUNTERS``) over the rounds;
* ``spans``: the host milliseconds of each ``bsbodp`` span, of the
  ``execute`` spans (``algo``) and of the scheduler (``sched``), for the
  profiled round and, apart, for the unprofiled rounds;
* ``trace``: the profiled round's device seconds and launches by compiled
  program (``by_program``, every program), the launches inside the
  harness's eval, and its idle time by the innermost host span covering
  each gap (``idle_by_label``). The program mirrors its tracer's spans onto
  the trace as ``repro.<name>`` events, so the gaps are labelled on the
  trace's own clock; ``repro.round …`` is the scheduler.

The profiled round's stop of the profiler and trace write count against
``--seconds`` (as in a ``--trace 1`` run), so a window of about 90 seconds
holds two or three unprofiled rounds after it.

A ``--trace 1`` run's per-layer metrics read the same spans, counters and
programs through the same code (``bench/program_obs.py``,
``trace_reduce.reduce_profile``'s ``by_program``); this adds the profiled
round apart from the others and the idle gaps by the program's labels.
The last line of standard output is one JSON object. Without a TPU it
exits with code 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import program_obs  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

EVAL = "bench.eval"
PROGRAM = "repro."
# always-on counters of the program (``global_registry``)
PROGRAM_COUNTERS = ("fl_jit_calls_total", "fl_stack_calls_total", "fl_h2d_bytes_total")


def program_label(name: str) -> str:
    """Idle-gap label of the program's span ``repro.<name>``: the label the
    harness gives the tracer's span (``round …`` is the scheduler)."""
    return "scheduler" if name.startswith("round ") else name


def split_profile(planes) -> dict:
    """The profiled round (``trace_reduce.WINDOW``) by compiled program
    (``trace_reduce.reduce_profile``'s ``by_program``) and by host label,
    with the reduction's window, busy time and launches. Device times are
    clipped to the window and moved onto the host clock as
    ``trace_reduce.reduce_profile`` does."""
    planes = list(planes)
    red = tr.reduce_profile(planes)
    window, spans, evals, executes = None, [], [], []
    for pl in planes:
        if not pl.name.startswith("/host"):
            continue
        for line in pl.lines:
            for e in line.events:
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if e.name == tr.HOST_EXECUTE:
                    executes.append(a)
                elif e.name == tr.WINDOW:
                    window = (a, b)
                elif e.name.startswith(PROGRAM):
                    spans.append((program_label(e.name[len(PROGRAM):]), a, b))
                elif e.name.startswith("bench.") and e.name != tr.MARKER:
                    spans.append((e.name[len("bench."):], a, b))
                    if e.name == EVAL:
                        evals.append((a, b))
    lo, hi = window
    devices = [pl for pl in planes if pl.name.startswith("/device:")
               and any(line.name == tr.OPS_LINE for line in pl.lines)]
    idle: dict[str, list] = defaultdict(lambda: [0.0, 0])
    eval_n, skew = 0, None
    for k, pl in enumerate(devices):
        starts = sorted(a for _, a, _ in tr._events(pl, tr.MODULES_LINE))
        if skew is None:
            skew = tr.clock_skew(sorted(executes), starts)
        for a in starts:
            a += skew
            if lo <= a < hi:
                eval_n += any(ea <= a < eb for ea, eb in evals)
        if k == 0:
            ivs = []
            for _, a, b in tr._events(pl, tr.OPS_LINE):
                a, b = max(a + skew, lo), min(b + skew, hi)
                if b > a:
                    ivs.append((a, b))
            gaps = tr.gaps(tr.union(ivs), lo, hi)
            for (a, b), lab in zip(gaps, tr.labels([(a + b) / 2 for a, b in gaps], spans)):
                idle[lab][0] += (b - a) * 1e-9
                idle[lab][1] += 1
    return {
        "by_program": red["by_program"],
        "eval_launches": eval_n / len(devices),
        "idle_by_label": dict(idle),
        "window_s": red["window_s"], "busy_s": red["busy_s"], "launches": red["launches"],
    }


def program_counters(per: dict) -> dict:
    """Each of ``PROGRAM_COUNTERS`` that the program registers, summed over
    its labels, from ``program_obs.per_round``'s changes."""
    out: dict[str, float] = {}
    for key, v in per.items():
        name = key.split("{", 1)[0]
        if name in PROGRAM_COUNTERS:
            out[name] = out.get(name, 0.0) + v
    return out


def span_ms_per_round(tracer, rounds) -> dict:
    """Host milliseconds per round over ``rounds`` of each ``bsbodp`` span
    by name (``program_obs.span_ms_per_round``), of the ``execute`` spans
    (``algo``) and of the scheduler (``sched``)."""
    out = {k: v for k, v in program_obs.span_ms_per_round(tracer, rounds).items()
           if k.startswith("bsbodp.")}
    out["sched"], out["algo"] = program_obs.sched_algo_ms(tracer, rounds)
    return out


def measure(cell, st: dict, seconds: float, profile: bool) -> dict:
    """The harness's window of whole rounds after its traced set-up ``st``,
    and with ``profile`` the first round under the profiler."""
    from bench import harness

    c0 = program_obs.counters()
    w = harness.window(cell, st, seconds, profile)
    per = program_obs.per_round(c0, program_obs.counters(), len(w["rounds"]))
    rounds = [x["round"] for x in w["rounds"]]
    out = {"rounds": rounds, "counters": program_counters(per), "spans": {}}
    prof = w["profiled"]
    if prof is not None:
        out["spans"]["profiled"] = span_ms_per_round(st["tracer"], [prof["round"]])
        rest = [r for r in rounds if r != prof["round"]]
        try:
            from jax.profiler import ProfileData

            planes = list(ProfileData.from_file(prof["path"]).planes)
        finally:
            shutil.rmtree(prof["tmp"], ignore_errors=True)
        out["trace"] = split_profile(planes)
    else:
        rest = rounds
    if rest:
        out["spans"]["unprofiled"] = span_ms_per_round(st["tracer"], rest)
    return out


def report(out: dict) -> None:
    from bench.harness import log

    for k, v in out["counters"].items():
        log(f"[split] counter {k}: {v:.1f}/round")
    for part, ms in out["spans"].items():
        for k, v in ms.items():
            log(f"[split] {part} span {k}: {v:.3f} ms/round")
    t = out.get("trace")
    if t is None:
        return
    for prog, v in sorted(t["by_program"].items(), key=lambda kv: -kv[1]["device_s"]):
        log(f"[split] program {prog}: {v['device_s'] * 1e3:.3f} ms device, "
            f"{v['launches']:.0f} launches")
    log(f"[split] programs {sum(v['device_s'] for v in t['by_program'].values()):.6f} s "
        f"device against busy {t['busy_s']:.6f} s; {t['launches']:.0f} launches, "
        f"{t['eval_launches']:.0f} in the eval")
    for lab, (sec, n) in sorted(t["idle_by_label"].items(), key=lambda kv: -kv[1][0]):
        log(f"[split] idle {lab}: {sec:.6f} s in {n} gaps")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    from bench import harness, spec
    from bench.run import tpu_devices

    cell = spec.load_cell(args.workload)
    if tpu_devices(cell.chips) is None:
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    st = harness.setup(cell, args.seed, True, T_START)
    out = dict(measure(cell, st, args.seconds, True),
               workload=args.workload, seed=args.seed)
    report(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
