"""Tests for the static-analysis pass: AST rules over the fixture corpus,
baseline suppression round-trip, and kernel-contract corruption checks."""
import dataclasses
import os

import pytest

from repro.analysis import kernel_contracts as kc
from repro.analysis import run_analysis
from repro.analysis.findings import Baseline, Finding, parse_allows
from repro.analysis.rules import RULES
from repro.analysis.visitor import scan_source

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
#: virtual path inside every rule's scope (and outside every exemption)
VPATH = "src/repro/sim/fixture.py"

ALL_RULES = sorted(RULES)


def _scan(name: str, rule_id: str, vpath: str = VPATH):
    with open(os.path.join(FIXTURES, name)) as f:
        return scan_source(f.read(), vpath, [RULES[rule_id]])


# -- AST rules over the fixture corpus ---------------------------------------


@pytest.mark.parametrize("rule_id", ALL_RULES)
def test_bad_fixture_is_flagged(rule_id):
    findings, _ = _scan(f"{rule_id.lower()}_bad.py", rule_id)
    assert findings, f"{rule_id} missed its violating fixture"
    assert all(f.rule == rule_id for f in findings)
    assert all(f.path == VPATH and f.line > 0 for f in findings)


@pytest.mark.parametrize("rule_id", ALL_RULES)
def test_ok_fixture_is_clean(rule_id):
    findings, _ = _scan(f"{rule_id.lower()}_ok.py", rule_id)
    assert findings == [], f"{rule_id} false positive: {findings}"


def test_scoping_rules_ignore_out_of_scope_paths():
    # DET001 only applies to signature-bearing code, not kernels
    findings, _ = _scan("det001_bad.py", "DET001",
                        vpath="src/repro/kernels/fixture.py")
    assert findings == []
    # ARCH002 exempts the registry implementation itself
    findings, _ = _scan("arch002_bad.py", "ARCH002",
                        vpath="src/repro/fl/api.py")
    assert findings == []


def test_inline_allow_suppresses_and_counts():
    findings, suppressed = _scan("det001_ok.py", "DET001")
    assert findings == []
    assert len(suppressed) == 2  # same-line and line-above annotations


def test_parse_allows_positions():
    allows = parse_allows(
        "x = 1\n"
        "t = clock()  # analysis: allow[DET001, DET002]\n"
        "# analysis: allow[OBS001]\n"
    )
    assert allows == {2: {"DET001", "DET002"}, 3: {"OBS001"}}


def test_expected_bad_finding_counts():
    expect = {"DET001": 3, "DET002": 4, "DET003": 3, "DET004": 4,
              "PERF001": 3, "ARCH001": 4, "ARCH002": 3, "OBS001": 3}
    for rule_id, want in expect.items():
        findings, _ = _scan(f"{rule_id.lower()}_bad.py", rule_id)
        assert len(findings) == want, (rule_id, findings)


# -- baseline round-trip -----------------------------------------------------


def test_baseline_round_trip(tmp_path):
    findings, _ = _scan("det002_bad.py", "DET002")
    path = str(tmp_path / "baseline.json")
    Baseline({f.key() for f in findings}).save(path)
    loaded = Baseline.load(path)
    new, grandfathered = loaded.split(findings)
    assert new == [] and len(grandfathered) == len(findings)
    # an unseen finding still fails
    extra = findings + [Finding("DET002", "src/repro/sim/other.py", 9, "x")]
    new, _ = loaded.split(extra)
    assert [f.path for f in new] == ["src/repro/sim/other.py"]


def test_missing_baseline_is_empty(tmp_path):
    assert Baseline.load(str(tmp_path / "nope.json")).keys == set()


# -- the repo itself is clean ------------------------------------------------


def test_repo_ast_scan_is_clean():
    findings, suppressed = run_analysis(kernels=False)
    assert findings == [], [f.render() for f in findings]
    # the four annotated host-timing sites in fl/ + the pre-run byzantine
    # label-noise derivation in sim/faults.py (DET004: the default_rng call
    # and the SeedSequence on its continuation line) + the deliberately
    # scalar migration draw loop in sim/churn.py (PERF001: legacy RNG
    # consumption order is part of the signature contract) + the seven
    # host-only perf_counter sites behind the engine's --profile-sim
    # gate (DET001: gauges, never event payloads)
    assert len(suppressed) == 14


# -- kernel contracts --------------------------------------------------------


SHAPES = kc.bench_shapes(os.path.join(os.path.dirname(__file__), "..",
                                      "BENCH_kernels.json"))


def test_kernel_contracts_pass_on_bench_shapes():
    findings = kc.check_all(os.path.join(os.path.dirname(__file__), "..",
                                         "BENCH_kernels.json"))
    assert findings == [], [f.render() for f in findings]


def test_trace_check_catches_contract_drift():
    c = kc.CONTRACTS["skr_rectify"]

    def wrong_abstract(shape):
        fn, specs, _ = c.abstract(shape)
        return fn, specs, {"out": (1, 2, 3)}

    bad = dataclasses.replace(c, abstract=wrong_abstract)
    findings = kc.check_trace(bad, SHAPES["skr_rectify"])
    assert [f.rule for f in findings] == ["KRN001"]


def test_divisibility_catches_corrupted_block():
    c = kc.CONTRACTS["skr_rectify"]
    shape = dict(SHAPES["skr_rectify"])

    def bad_geometry(s):
        geo = c.geometry(s)
        padded, _ = geo.tiled["p"]
        geo.tiled["p"] = (padded, (1, 8, 100))  # 1024 % 100 != 0
        geo.lane_blocks = [("p", 100)]  # and 100 % 128 != 0
        return geo

    bad = dataclasses.replace(c, geometry=bad_geometry)
    rules = {f.rule for f in kc.check_divisibility(bad, shape)}
    assert rules == {"KRN002"}
    assert kc.check_divisibility(c, shape) == []


def test_divisibility_catches_misaligned_minor_pair():
    """A (1, 8) block over a (B, N) row array — the label layout the TPU
    lowering refuses — is flagged even though 8 divides N."""
    c = kc.CONTRACTS["skr_rectify"]
    shape = dict(SHAPES["skr_rectify"])

    def row_geometry(s):
        geo = c.geometry(s)
        (B, Np, _), _ = geo.tiled["label"]
        geo.tiled["label"] = ((B, Np), (1, 8))
        return geo

    bad = dataclasses.replace(c, geometry=row_geometry)
    findings = kc.check_divisibility(bad, shape)
    assert {f.rule for f in findings} == {"KRN002"}
    assert any("minor block dim 8 " in f.message for f in findings)
    assert kc.check_divisibility(c, shape) == []


def test_vmem_budget_is_enforced():
    c = kc.CONTRACTS["flash_attention"]
    shape = SHAPES["flash_attention"]
    assert kc.check_vmem(c, shape) == []
    findings = kc.check_vmem(c, shape, budget=1024)
    assert [f.rule for f in findings] == ["KRN003"]


def test_fp32_policy_catches_low_precision_scratch():
    c = kc.CONTRACTS["flash_attention"]
    assert kc.check_fp32_accum(c) == []
    corrupted = (
        "import jax.numpy as jnp\n"
        "import jax.experimental.pallas.tpu as pltpu\n"
        "def _kernel(q_ref, o_ref, acc):\n"
        "    o_ref[...] = q_ref[...] @ q_ref[...].T\n"  # no fp32 cast
        "def build():\n"
        "    return pltpu.VMEM((8, 128), jnp.bfloat16)\n"  # low-prec scratch
    )
    rules = [f.rule for f in kc.check_fp32_accum(c, source=corrupted)]
    assert rules == ["KRN004", "KRN004"]


def test_vjp_pairing_flags_undifferentiable_kernel():
    ok = kc.check_vjp_pairing(kc.CONTRACTS["distill_loss"],
                              SHAPES["distill_loss"])
    assert ok == []
    flipped = dataclasses.replace(kc.CONTRACTS["skr_rectify"],
                                  differentiable=True)
    findings = kc.check_vjp_pairing(flipped, SHAPES["skr_rectify"])
    assert [f.rule for f in findings] == ["KRN005"]


def test_wrapper_pairing_flags_missing_wrapper():
    bad = dataclasses.replace(kc.CONTRACTS["distill_loss"],
                              wrapper="no_such_wrapper")
    findings = kc.check_vjp_pairing(bad, SHAPES["distill_loss"])
    assert "KRN005" in [f.rule for f in findings]


# -- CLI ---------------------------------------------------------------------


def test_cli_explain_and_clean_run(capsys):
    from repro.analysis.__main__ import main

    assert main(["--explain", "DET001"]) == 0
    assert main(["--explain", "KRN002"]) == 0
    assert main(["--explain", "NOPE99"]) == 2
    assert main(["--no-kernels"]) == 0
    out = capsys.readouterr().out
    assert "OK:" in out


def test_cli_flags_violations_in_scanned_path(tmp_path, capsys):
    from repro.analysis.__main__ import main

    bad_root = tmp_path / "src" / "repro" / "sim"
    bad_root.mkdir(parents=True)
    (bad_root / "clockful.py").write_text(
        "import time\n\ndef f():\n    return time.time()\n")
    assert main(["--root", str(tmp_path), "--no-kernels"]) == 1
    out = capsys.readouterr().out
    assert "DET001" in out
