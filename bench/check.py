"""The numbers that decide ``correct``: the program's first round against the
plain reference's (``bench.reference``).

Both sides are reduced to the same summary (``summarize``): per node and per
parameter leaf, the norm of the change after the node's first dispatch group
and after the whole first round; of the first three student steps of the
node's first direction as student, each step's loss, the first gradient's
norm as Adam's second moment holds it after the first step (the root of the
sum of ``v``), and the norm of the change after the third; the step count
at the round's end, the leaves' embedding stores, and the cloud's eval
logits before and after the round. ``readings`` compares two summaries. Norms are compared as the gap between the two
norms, never as the norm of the difference: a first Adam step moves every
element by about the learning rate whatever the sign of its gradient, so
rounding flips signs without changing the norms.

Each side reduces a snapshot of a node's state to these norms when it takes
it (``change_norms``, ``v_norms``), one leaf at a time, and keeps the
parameters before the round as a host copy (``to_host``): between snapshots
the check holds no device array of a node's state, so a tier whose training
state fills the chip can be checked. Scalars are read with ``.item()``:
``float()`` of a device array keeps another device array alive.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

# A leaf whose first gradient in the reference is under this share of the
# node's median leaf gradient moves under Adam by round-off alone; it is left
# out of the norm gaps.
DEAD_LEAF = 1e-3


def _paths(tree):
    return [jax.tree_util.keystr(k) for k, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _v_norm(x):
    return jnp.sqrt(jnp.sum(x.astype(jnp.float32)))


@jax.jit
def _diff_norm(x, y):
    return jnp.linalg.norm((x.astype(jnp.float32) - y.astype(jnp.float32)).ravel())


def pick(x, b):
    """A leaf, or its row ``b`` of a coalesced group's stacked output."""
    return x if b is None else x[b]


def to_host(tree):
    """A host copy of a tree of device arrays, copied so that it keeps no
    device buffer alive where host and device share memory (the CPU)."""
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def change_norms(tree, init, b=None) -> dict[str, float]:
    """Per leaf path: the norm of ``tree - init`` in float32, ``init`` a host
    copy brought to the device one leaf at a time; ``b`` picks a row of a
    stacked ``tree``."""
    return {k: _diff_norm(pick(x, b), y).item() for k, x, y in
            zip(_paths(init), jax.tree.leaves(tree), jax.tree.leaves(init))}


def v_norms(tree, b=None) -> dict[str, float]:
    """Per leaf path: the root of the sum of Adam's ``v`` (the first
    gradient's norm after one step)."""
    return {k: _v_norm(pick(x, b)).item() for k, x in zip(_paths(tree), jax.tree.leaves(tree))}


def end_norms(params: dict, opt: dict, init: dict) -> dict:
    """node -> (per-leaf change norms, AdamW step count) at the round's end."""
    return {v: (change_norms(params[v], init[v]), opt[v]["step"].item()) for v in params}


def summarize(init, first, end, stores, logits_init, logits_round, steps, grad_norms=None,
              group0=()):
    """One side of the comparison, from snapshots already reduced.

    ``init``: node -> host copy of its parameters before the round;
    ``first``: node -> per-leaf change norms after its first dispatch group;
    ``end``: node -> (per-leaf change norms, step count) after the round
    (``end_norms``); ``stores``: leaf -> embeddings; logits: (N, classes);
    ``steps``: node -> (the losses of the first steps of its first
    direction as student, ``v_norms`` after the first, ``change_norms``
    after the last); ``grad_norms``: node -> per-leaf norms of its first
    gradient, and ``group0``: the nodes of the round's first dispatch group
    (the reference's side only)."""
    return {"init": {v: jax.tree.leaves(p) for v, p in init.items()},
            "first_delta": dict(first),
            "end_delta": {v: d for v, (d, _) in end.items()},
            "end_step": {v: n for v, (_, n) in end.items()},
            "loss": {v: [float(x) for x in losses] for v, (losses, _, _) in steps.items()},
            "v1": {v: v1 for v, (_, v1, _) in steps.items()},
            "delta3": {v: d3 for v, (_, _, d3) in steps.items()},
            "stores": {v: np.array(e, np.float32, copy=True) for v, e in stores.items()},
            "logits_init": np.asarray(logits_init, np.float32),
            "logits_round": np.asarray(logits_round, np.float32),
            "grad": {v: dict(zip(_paths(init[v]), np.asarray(g, np.float64).tolist()))
                     for v, g in (grad_norms or {}).items()},
            "group0": set(group0)}


def _kind(v: str) -> str:
    return "cloud" if v == "cloud" else "edge" if v.startswith("edge") else "client"


def _tiers(v: str, ref: dict) -> list[str]:
    """The tiers a node's readings count in. The clients of the round's
    first group also count as ``client0``: the only clients whose teacher
    has not yet been trained on either side, so that their first steps
    compare the step and not two teachers that have parted."""
    kind = _kind(v)
    return [kind, "client0"] if kind == "client" and v in ref["group0"] else [kind]


def _leaf_gaps(side: dict, ref: dict, grad: dict) -> list[float]:
    """Per leaf: |norm(side) - norm(ref)| over max(norm(ref) of the leaf,
    median leaf norm of ref), on leaves the reference's gradient moves."""
    gmed = statistics.median(grad.values())
    keep = [k for k in ref if grad[k] >= DEAD_LEAF * gmed]
    med = statistics.median(ref[k] for k in keep)
    return [abs(side[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep]


def _rel_logits(z, zr) -> float:
    return float(np.max(np.abs(z - zr)) / max(float(np.sqrt(np.mean(zr ** 2))), 1e-30))


def _worst(a: float, b: float) -> float:
    """The larger of two readings; an unreadable one (NaN) stays."""
    return a if a != a or b <= a else b


def readings(side: dict, ref: dict) -> dict[str, float]:
    """Every number compared, worst over the nodes of each tier. A node the
    reference records and the program does not reads NaN."""
    out: dict[str, float] = {}
    out["init"] = max(float(np.max(np.abs(a.astype(np.float64) - b.astype(np.float64))))
                      for v in ref["init"] for a, b in zip(side["init"][v], ref["init"][v]))
    out["embed"] = max(float(np.max(np.abs(side["stores"][v] - ref["stores"][v])))
                       for v in ref["stores"])
    out["steps"] = float(sum(side["end_step"][v] != ref["end_step"][v]
                             for v in ref["end_step"]))
    nan = float("nan")
    for v, lr in ref["loss"].items():
        ls = side["loss"].get(v, [])
        gap = (max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(ls, lr))
               if len(ls) == len(lr) else nan)
        for t in _tiers(v, ref):
            out[f"loss.{t}"] = _worst(out.get(f"loss.{t}", 0.0), gap)
    for name, key in (("delta", "first_delta"), ("round", "end_delta"),
                      ("grad1", "v1"), ("delta3", "delta3")):
        for v in ref[key]:
            gaps = (_leaf_gaps(side[key][v], ref[key][v], ref["grad"][v])
                    if v in side[key] else [nan])
            for t in _tiers(v, ref):
                for k, g in ((f"{name}.{t}", max(gaps)),
                             (f"{name}_med.{t}", statistics.median(gaps))):
                    out[k] = _worst(out.get(k, 0.0), g)
    out["eval.init"] = _rel_logits(side["logits_init"], ref["logits_init"])
    out["eval.round"] = _rel_logits(side["logits_round"], ref["logits_round"])
    return out


def verdict(values: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """(correct, {name: [value, limit]}) over the compared numbers. A number
    that could not be read (NaN) fails."""
    compared = {k: [values[k], limits[k]] for k in limits}
    ok = all(v == v and v <= lim for v, lim in compared.values())
    return ok, compared
