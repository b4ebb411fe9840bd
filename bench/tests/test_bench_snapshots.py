"""The check reduces each snapshot of a node's state to its norms when it
takes it. At the checked round's eval, after set-up, and while the
reference's state is alive, the device holds that side's own state, the
autoencoder and the stores, and no array of the check's. The summaries and
readings are those that the check gave when it kept whole snapshots
(``fixtures/summaries_tiny.json``, recorded at seeds 5 and 2**31 + 11
before the snapshots were reduced)."""
import gc
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from bench_tiny import tiny_cell

from bench import check, harness, reference

FIXTURE = json.loads((Path(__file__).parent / "fixtures" / "summaries_tiny.json").read_text())
SEEDS = [5, 2**31 + 11]


def _held_by_others(trees, stores, before) -> int:
    """Bytes of live device arrays that are neither in ``before`` (alive
    before the run, from other tests in this process) nor a leaf of
    ``trees`` nor equal to one (the program's compiled decode keeps the
    decoder as constants) nor to a store (on the CPU a store is a view of
    the device array it was read from)."""
    leaves = [x for t in trees for x in jax.tree.leaves(t)]
    own = {id(x) for x in leaves + before}
    copies = [np.asarray(x) for x in leaves] + [np.asarray(s) for s in stores]

    def accounted(a):
        if id(a) in own:
            return True
        v = np.asarray(a)
        return any(c.shape == v.shape and c.dtype == v.dtype and np.array_equal(c, v)
                   for c in copies)

    return sum(a.nbytes for a in jax.live_arrays() if not accounted(a))


def _numbers(s: dict) -> dict:
    """Every number of a summary that the readings are computed from."""
    return {"init": {v: [float(np.linalg.norm(np.asarray(x, np.float64))) for x in leaves]
                     for v, leaves in s["init"].items()},
            **{k: s[k] for k in ("first_delta", "end_delta", "end_step", "loss", "v1", "delta3",
                                 "grad")},
            "stores": {v: float(np.linalg.norm(np.asarray(e, np.float64)))
                       for v, e in s["stores"].items()},
            **{k: np.linalg.norm(np.asarray(s[k], np.float64), axis=-1).tolist()
               for k in ("logits_init", "logits_round")}}


@pytest.fixture(scope="module", params=SEEDS, ids=["seed5", "seed2p31p11"])
def run(request):
    seed = request.param
    cell = tiny_cell()
    out = {"seed": seed}
    before = jax.live_arrays()  # kept alive, so that no new array takes their ids
    evaluate = harness.Evaluator.__call__

    def at_the_checked_rounds_eval(self):
        if "round_extra" not in out:
            tr = self.trainer
            out["round_extra"] = _held_by_others(
                (tr.params, tr.opt, tr.skr, tr.auto), [tr.embeddings[v][0] for v in tr.client_data],
                before)
        return evaluate(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.Evaluator, "__call__", at_the_checked_rounds_eval)
        st = harness.setup(cell, seed, False, time.perf_counter())
    tr = st["trainer"]
    out.update(setup_extra=_held_by_others(
        (tr.params, tr.opt, tr.skr, tr.auto, st["auto"]),
        [tr.embeddings[v][0] for v in tr.client_data], before))
    args = st["groups"], st["inputs"], st["auto"]
    side = st["side"]
    del st, tr
    gc.collect()

    refs = []
    post_init, summarize = reference.Reference.__post_init__, check.summarize

    def keep(self):
        post_init(self)
        refs.append(self)

    def measured(*a, **kw):
        r = refs[-1]
        out["reference_extra"] = _held_by_others(
            (r.params, r.opt, r.skr, r.auto, args[2]), [], before)
        return summarize(*a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference.Reference, "__post_init__", keep)
        mp.setattr(check, "summarize", measured)
        ref = harness.run_reference(cell, seed, *args)
    refs.clear()
    out.update(side=side, ref=ref)
    return out


def test_at_the_checked_rounds_eval_the_device_holds_no_array_of_the_checks(run):
    assert run["round_extra"] == 0


def test_after_set_up_the_device_holds_no_array_of_the_checks(run):
    assert run["setup_extra"] == 0


def test_beside_the_references_state_the_device_holds_no_array_of_the_checks(run):
    assert run["reference_extra"] == 0


def _close(got, want, path=""):
    if isinstance(want, dict):
        assert set(got) == set(want), path
        for k in want:
            _close(got[k], want[k], f"{path}/{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), path
        for i, (a, b) in enumerate(zip(got, want)):
            _close(a, b, f"{path}/{i}")
    else:
        assert got == pytest.approx(want, rel=1e-6, abs=0), path


@pytest.mark.parametrize("side", ["program", "reference"])
def test_summaries_are_those_of_whole_snapshots(run, side):
    got = _numbers(run["side" if side == "program" else "ref"])
    _close(json.loads(json.dumps(got)), FIXTURE[str(run["seed"])][side])


def test_readings_are_those_of_whole_snapshots(run):
    _close(check.readings(run["side"], run["ref"]), FIXTURE[str(run["seed"])]["readings"])
