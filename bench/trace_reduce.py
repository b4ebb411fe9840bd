"""Reduces a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The window is the host annotation ``bench.round`` that the harness wraps
around the profiled round. On each device plane (``/device:...``) the
operations of the ``XLA Ops`` line give the busy intervals, whose union is
the busy time, and the events of the ``XLA Modules`` line are the launches
of compiled programs. An operation is named by its program and its own
short name (``jit_fn/fusion.4``); each program's operations and launches
are also summed apart (``by_program``). The device's clock runs apart from
the host's (about 1.5 ms behind on a v5e): device events are moved onto the
host clock by the median gap between each host ``tpu::System::Execute`` and
the program launch it issued. The gaps between busy intervals are labelled by the innermost
host span that covers their midpoint: the harness's own annotations from
the trace, and the program's tracer spans, which the harness places on the
trace's clock through the ``bench.clock`` marker.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict

WINDOW = "bench.round"
MARKER = "bench.clock"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_EXECUTE = "tpu::System::Execute"


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi) around merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def label_of(t, spans):
    """Label of the shortest span (label, start, end) covering t."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "harness"


def labels(ts, spans):
    """For each time in ``ts``, the label of the shortest span (label,
    start, end) covering it, the earliest listed of equal ones, or
    ``harness``: ``label_of`` in one sweep over the times in order, the
    spans sorted once by start and the open ones kept in a heap by length."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    out = ["harness"] * len(ts)
    heap: list[tuple[float, int, float]] = []
    j = 0
    for k in sorted(range(len(ts)), key=ts.__getitem__):
        t = ts[k]
        while j < len(order) and spans[order[j]][1] <= t:
            i = order[j]
            heapq.heappush(heap, (spans[i][2] - spans[i][1], i, spans[i][2]))
            j += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        if heap:
            out[k] = spans[heap[0][1]][0]
    return out


def short_name(name: str) -> str:
    """``%fusion.4 = (f32[...]) fusion(...)`` -> ``fusion.4``;
    ``jit_fn(123)`` -> ``jit_fn``."""
    return name.split(" = ")[0].lstrip("%").split("(")[0]


def clock_skew(host_issues, device_starts) -> float:
    """Nanoseconds to add to device times to put them on the host clock: the
    median of host issue minus device start over launches paired in order,
    or 0 when the two counts differ and launches cannot be paired."""
    if not device_starts or len(host_issues) != len(device_starts):
        return 0.0
    d = sorted(h - s for h, s in zip(host_issues, device_starts))
    return d[len(d) // 2]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce_profile(planes, *, marker_perf=None, host_spans=(), top=10) -> dict:
    """Device numbers of the window. ``planes`` is the ``planes`` of a
    ``jax.profiler.ProfileData``; ``host_spans`` are (label, start, end) in
    ``perf_counter`` seconds, placed on the trace's clock by the marker
    annotation recorded at ``marker_perf``."""
    planes = list(planes)
    window = marker = None
    annotations, executes = [], []
    for pl in planes:
        if not pl.name.startswith("/host"):
            continue
        for line in pl.lines:
            for e in line.events:
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if e.name == HOST_EXECUTE:
                    executes.append(a)
                elif e.name == WINDOW:
                    window = (a, b)
                elif e.name == MARKER:
                    marker = a
                elif e.name.startswith("bench."):
                    annotations.append((e.name[len("bench."):], a, b))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = window
    spans = list(annotations)
    if marker is not None and marker_perf is not None:
        spans += [(name, marker + (a - marker_perf) * 1e9, marker + (b - marker_perf) * 1e9)
                  for name, a, b in host_spans]
    devices = [pl for pl in planes if pl.name.startswith("/device:")
               and any(line.name == OPS_LINE for line in pl.lines)]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")
    busy_ns, launches = [], []
    op_time: dict[str, float] = defaultdict(float)
    prog_s: dict[str, float] = defaultdict(float)
    prog_n: dict[str, int] = defaultdict(int)
    idle: dict[str, float] = defaultdict(float)
    idle_n: dict[str, int] = defaultdict(int)
    skews = []
    for k, pl in enumerate(devices):
        modules = sorted(_events(pl, MODULES_LINE), key=lambda m: m[1])
        skew = clock_skew(sorted(executes), [m[1] for m in modules]) if k == 0 else skews[0]
        skews.append(skew)
        modules = [(n, a + skew, b + skew) for n, a, b in modules]
        starts = [m[1] for m in modules]
        ivs = []
        for name, a, b in _events(pl, OPS_LINE):
            a, b = max(a + skew, lo), min(b + skew, hi)
            if b > a:
                ivs.append((a, b))
                j = bisect.bisect_right(starts, a) - 1
                prog = short_name(modules[j][0]) if j >= 0 and modules[j][2] >= a else "?"
                op_time[f"{prog}/{short_name(name)}"] += (b - a) * 1e-9
                prog_s[prog] += (b - a) * 1e-9
        merged = union(ivs)
        busy_ns.append(sum(b - a for a, b in merged))
        launched = [short_name(name) for name, a, _ in modules if lo <= a < hi]
        launches.append(len(launched))
        for prog in launched:
            prog_n[prog] += 1
        if k == 0:
            idle_ivs = gaps(merged, lo, hi)
            mids = [(a + b) / 2 for a, b in idle_ivs]
            for (a, b), lab in zip(idle_ivs, labels(mids, spans)):
                idle[lab] += (b - a) * 1e-9
                idle_n[lab] += 1
    n = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) / n * 1e-9,
        "launches": sum(launches) / n,
        "devices": n,
        "clock_skew_s": skews[0] * 1e-9,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([f"{k} ({idle_n[k]} gaps)", v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
        # device seconds (its operations summed, so overlapping ones count
        # twice) and launches of each compiled program
        "by_program": {p: {"device_s": prog_s[p] / n, "launches": prog_n[p] / n}
                       for p in sorted(set(prog_s) | set(prog_n))},
    }


def reduce_file(path: str, **kw) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path).planes, **kw)
