"""Trace-contract tier tests (analysis/contracts + analysis/trace_lint).

The registry is the ONE implementation of the repo's jaxpr/HLO pins:
contracts T001-T010 over the shipped entry points, with
expect="violates" targets keeping every predicate demonstrably sensitive.
The migrated wave-loop / EFB-routing pins live in their own test
files (test_compacted_row_index.py, test_efb_bundlespace.py) and
assert through this registry; here we cover the linear-fit pins added for
the piecewise-linear leaves PR, the donation/collective/host-transfer
contracts, the sensitivity machinery, and the CLI (--trace, --load,
--update-baseline, stale entries, SARIF)."""
import json
import os
import subprocess
import sys

import pytest

from lightgbm_tpu.analysis.contracts import (CONTRACTS, Target,
                                             build_program, contract,
                                             evaluate, evaluate_target)
from lightgbm_tpu.analysis.contracts import checks as C
from lightgbm_tpu.analysis.contracts import jaxpr_utils as ju
import lightgbm_tpu.analysis.contracts.entries  # noqa: F401  (registers)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixtures", "tpu_lint", "trace_violations.py")


def _cell(cid, shape_class):
    c = CONTRACTS[cid]
    t = next(t for t in c.targets if t.shape_class == shape_class)
    return c, t, build_program(c.entry, shape_class)


# ------------------------------------------------------------ registry shape

def test_every_contract_target_has_a_builder():
    from lightgbm_tpu.analysis.contracts import PROGRAM_BUILDERS
    for cid, c in CONTRACTS.items():
        if cid.startswith("TX"):
            continue
        for t in c.targets:
            assert (c.entry, t.shape_class) in PROGRAM_BUILDERS, \
                f"{cid}: no builder for {c.entry}@{t.shape_class}"


def test_entry_points_are_the_shipped_callables():
    from lightgbm_tpu.analysis.contracts import get_entry
    from lightgbm_tpu import grower
    from lightgbm_tpu.ops import linear, predict
    assert get_entry("grower.wave_body") is grower.grow_tree
    assert get_entry("grower.stream_legs") is grower.StreamedGrower
    assert get_entry("linear.moments") is linear.accumulate_leaf_moments
    assert get_entry("linear.fit_leg") is linear.fit_linear_leaves
    assert get_entry("predict.forest_walk") is predict.forest_walk_leaves


# ------------------------------------------------------- linear-fit pins

def test_linear_moment_accumulation_is_gather_free():
    """PR-14 pin: the per-leaf normal-equation moments accumulate through
    the one-hot chunk contraction — no per-row feature gather."""
    c, t, program = _cell("T008", "linear")
    assert not ju.has_primitive(program.jaxpr, "gather")
    assert evaluate(c, t, program) == []


def test_linear_fit_has_exactly_one_batched_cholesky():
    c, t, program = _cell("T009", "linear")
    assert ju.count_primitive(program.jaxpr, "cholesky") == 1
    assert evaluate(c, t, program) == []


# ------------------------------------------------- shipped contract sweep

@pytest.mark.parametrize("cid", sorted(c for c in CONTRACTS
                                       if not c.startswith("TX")))
def test_shipped_contract_holds_on_every_target(cid):
    """Every shipped contract evaluates clean on every target — including
    the violates targets, whose check failure is the expected outcome."""
    c = CONTRACTS[cid]
    for t in c.targets:
        program = build_program(c.entry, t.shape_class)
        assert evaluate(c, t, program) == [], \
            f"{cid} @ {t.shape_class} reported findings"


def test_violates_targets_actually_violate():
    """The sensitivity arms really fail a check — otherwise evaluate()
    would have reported 'sensitivity lost' above, but assert the raw
    failures directly too."""
    for cid, shape_class in [("T002", "bundled_unpack")]:
        c, t, program = _cell(cid, shape_class)
        assert t.expect == "violates"
        assert evaluate_target(c, program), \
            f"{cid}: parity arm {shape_class} no longer violates"


def test_lost_sensitivity_is_reported():
    """A violates target whose program passes every check must surface a
    'sensitivity lost' finding."""
    c, _t, _p = _cell("T001", "serial")
    clean_program = build_program("grower.wave_body", "serial")
    findings = evaluate(c, Target("serial", "violates"), clean_program)
    assert len(findings) == 1
    fingerprint, message = findings[0]
    assert fingerprint.endswith(":sensitivity")
    assert "sensitivity lost" in message


# ------------------------------------------------ donation / collectives

def test_train_step_donation_aliases_in_hlo():
    c, t, program = _cell("T005", "serial")
    assert program.donate_argnums == (2, 3)
    assert ju.hlo_alias_count(program.hlo_text()) >= 1
    assert evaluate(c, t, program) == []


def test_data_parallel_collectives_match_cost_model():
    c, t, program = _cell("T003", "data8")
    present = ju.primitive_names(program.jaxpr)
    assert {"psum", "reduce_scatter", "all_gather"} <= present
    assert evaluate(c, t, program) == []


def test_hlo_alias_count_parses_nested_braces():
    s = ("HloModule jit_f, input_output_alias={ {0}: (8, {}, may-alias), "
         "{1}: (2, {}, must-alias) }, entry_computation_layout="
         "{(f32[8]{0})->f32[8]{0}}")
    assert ju.hlo_alias_count(s) == 2
    assert ju.hlo_alias_count("HloModule jit_f") == 0


# -------------------------------------------------- planted violations

def test_planted_fixture_violations_fire():
    """--load fixture: one violating cell per check kind, all five fire.
    TX94 (a loop body with a row-sized scatter) is what keeps T001's check,
    RowPassesInLoops, demonstrably sensitive: no shipped arm violates it
    since the carried leaf partition was deleted."""
    import runpy
    runpy.run_path(FIXTURE, run_name="trace_fixture_test")
    expected = {"TX90": "forbidden-primitive", "TX91": "required-collective",
                "TX92": "dtype", "TX93": "donation", "TX94": "row-pass"}
    assert any(isinstance(chk, C.RowPassesInLoops)
               for chk in CONTRACTS["T001"].checks) and all(
        t.expect == "clean" for t in CONTRACTS["T001"].targets)
    for cid, kind in expected.items():
        c = CONTRACTS[cid]
        t = c.targets[0]
        program = build_program(c.entry, t.shape_class)
        findings = evaluate(c, t, program)
        assert findings, f"{cid}: planted violation did not fire"
        assert findings[0][0].endswith(":" + kind)


# ----------------------------------------------------------------- CLI

def _run_trace_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "--trace", *argv],
        cwd=REPO, capture_output=True, text=True, timeout=600)


def test_cli_planted_violations_gate_exit(tmp_path):
    r = _run_trace_cli("--load", FIXTURE,
                       "--select", "TX90,TX91,TX92,TX93,TX94",
                       "--format", "json")
    assert r.returncode == 1, r.stderr
    data = json.loads(r.stdout)
    kinds = {f["snippet"].rsplit(":", 1)[1] for f in data["findings"]}
    assert kinds == {"forbidden-primitive", "required-collective",
                     "dtype", "donation", "row-pass"}


def test_cli_update_baseline_and_stale_detection(tmp_path):
    base = tmp_path / "trace_base.json"
    r = _run_trace_cli("--load", FIXTURE, "--select", "TX90",
                       "--baseline", str(base), "--update-baseline")
    assert r.returncode == 0, r.stderr
    entries = json.load(open(base))["findings"]
    assert len(entries) == 1 and entries[0]["rule"] == "TX90"
    # baselined violation no longer gates
    r = _run_trace_cli("--load", FIXTURE, "--select", "TX90",
                       "--baseline", str(base))
    assert r.returncode == 0, r.stdout + r.stderr
    # without the fixture the baselined cell disappears -> entry is stale
    r = _run_trace_cli("--select", "T010", "--baseline", str(base))
    assert r.returncode == 1
    assert "stale baseline" in r.stdout


def test_cli_sarif_output():
    r = _run_trace_cli("--load", FIXTURE, "--select", "TX90",
                       "--format", "sarif", "--no-baseline")
    assert r.returncode == 1, r.stderr
    sarif = json.loads(r.stdout)
    assert sarif["version"] == "2.1.0"
    results = sarif["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "TX90"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].startswith("trace://")
