"""Tests for the repro.analysis static-analysis pass.

Covers: one-violation-per-rule fixtures (each rule fires exactly once), the
zero-new-findings gate over ``src/``, inline ``# repro: noqa[RULE]`` and
baseline suppression, CLI exit codes, and the @audited_solver contract.
"""
from __future__ import annotations

import os
import pathlib

import numpy as np
import pytest

from repro.analysis import (
    all_rules,
    analyze_file,
    analyze_paths,
    load_baseline,
    new_findings,
    write_baseline,
)
from repro.analysis.__main__ import main as analysis_main

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = pathlib.Path(__file__).resolve().parent / "analysis_fixtures"

FIXTURE_CASES = [
    ("d101_set_iteration.py", "D101"),
    ("d102_float_time_eq.py", "D102"),
    ("d103_unseeded_rng.py", "D103"),
    ("d104_wall_clock.py", "D104"),
    ("j201_host_sync.py", "J201"),
    ("j202_tracer_branch.py", "J202"),
    ("j203_pallas_contract.py", "J203"),
    ("c301_unaudited_solver.py", "C301"),
    ("c302_mutable_default.py", "C302"),
    ("c303_bare_assert.py", "C303"),
    ("c304_unregistered_backend.py", "C304"),
    ("c305_swallowed_exception.py", "C305"),
    ("c306_wall_clock_import.py", "C306"),
]


# ---------------------------------------------------------------------------
# Rule firing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fname,rule", FIXTURE_CASES)
def test_fixture_fires_exactly_once(fname, rule):
    findings = analyze_file(str(FIXTURES / fname), all_rules())
    assert [f.rule for f in findings] == [rule], [f.format() for f in findings]


def test_fixture_cases_cover_every_rule():
    assert sorted(r for _, r in FIXTURE_CASES) == sorted(
        r.rule_id for r in all_rules()
    )


def test_finding_format_is_file_line_rule_message():
    findings = analyze_file(str(FIXTURES / "c303_bare_assert.py"), all_rules())
    out = findings[0].format()
    path, line, col, rest = out.split(":", 3)
    assert path.endswith("c303_bare_assert.py")
    assert int(line) > 0 and int(col) > 0
    assert rest.strip().startswith("C303 ")


def test_syntax_error_reported_not_raised(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    findings = analyze_file(str(bad), all_rules())
    assert [f.rule for f in findings] == ["E001"]


# ---------------------------------------------------------------------------
# The gate: src/ stays clean
# ---------------------------------------------------------------------------


def test_src_tree_has_no_new_findings():
    findings = analyze_paths([str(REPO / "src")])
    baseline = load_baseline(str(REPO / "analysis_baseline.txt"))
    fresh = new_findings(findings, baseline)
    assert fresh == [], "\n".join(f.format() for f in fresh)


def test_rules_scope_real_tree_paths():
    # Path-scoped rules must not leak outside their layer: a service-scoped
    # rule does not apply to kernels and vice versa.
    d_rule = next(r for r in all_rules() if r.rule_id == "D101")
    j_rule = next(r for r in all_rules() if r.rule_id == "J201")
    assert d_rule.applies("src/repro/service/scheduler.py")
    assert not d_rule.applies("src/repro/kernels/waterfill.py")
    assert j_rule.applies("src/repro/kernels/waterfill.py")
    assert j_rule.applies("src/repro/core/jax_solve.py")  # jitted solve tier
    assert not j_rule.applies("src/repro/service/scheduler.py")
    # Fixtures (no repro/ in the path) get every rule.
    assert d_rule.applies("tests/analysis_fixtures/d101_set_iteration.py")
    assert j_rule.applies("tests/analysis_fixtures/j201_host_sync.py")


# ---------------------------------------------------------------------------
# Suppression: inline noqa and the baseline ratchet
# ---------------------------------------------------------------------------


def _write(tmp_path, body):
    p = tmp_path / "snippet.py"
    p.write_text(body)
    return str(p)


def test_noqa_with_matching_rule_suppresses(tmp_path):
    path = _write(tmp_path, "import time  # repro: noqa[C306]\n"
                            "now = time.time()  # repro: noqa[D104]\n")
    assert analyze_file(path, all_rules()) == []


def test_noqa_with_wrong_rule_does_not_suppress(tmp_path):
    path = _write(tmp_path, "import time  # repro: noqa[C306]\n"
                            "now = time.time()  # repro: noqa[D101]\n")
    assert [f.rule for f in analyze_file(path, all_rules())] == ["D104"]


def test_bare_noqa_suppresses_everything_on_line(tmp_path):
    path = _write(tmp_path, "import time  # repro: noqa[C306]\n"
                            "now = time.time()  # repro: noqa\n")
    assert analyze_file(path, all_rules()) == []


def test_baseline_roundtrip_suppresses_known_findings(tmp_path):
    findings = analyze_paths([str(FIXTURES)])
    assert len(findings) == len(FIXTURE_CASES)
    baseline_path = tmp_path / "baseline.txt"
    write_baseline(str(baseline_path), findings)
    baseline = load_baseline(str(baseline_path))
    assert new_findings(findings, baseline) == []


def test_baseline_is_a_ratchet_not_a_blanket(tmp_path):
    # Baseline one D104; a second one in the same file must still be new.
    path = _write(tmp_path, "import time  # repro: noqa[C306]\na = time.time()\n")
    first = analyze_file(path, all_rules())
    baseline_path = tmp_path / "baseline.txt"
    write_baseline(str(baseline_path), first)
    with open(path, "a") as f:
        f.write("b = time.time()\n")
    both = analyze_file(path, all_rules())
    fresh = new_findings(both, load_baseline(str(baseline_path)))
    assert [f.rule for f in fresh] == ["D104"] and fresh[0].line == 3


def test_malformed_baseline_raises(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text("only-two fields\n")
    with pytest.raises(ValueError):
        load_baseline(str(p))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_exits_nonzero_on_fixture_violations(capsys):
    assert analysis_main([str(FIXTURES)]) == 1
    out = capsys.readouterr().out
    for _, rule in FIXTURE_CASES:
        assert rule in out


def test_cli_exits_zero_on_clean_file(tmp_path, capsys):
    path = _write(tmp_path, "x = 1\n")
    assert analysis_main([path]) == 0


def test_cli_exits_zero_with_baseline(tmp_path, capsys):
    baseline = tmp_path / "baseline.txt"
    assert analysis_main(
        [str(FIXTURES), "--baseline", str(baseline), "--write-baseline"]
    ) == 0
    assert analysis_main([str(FIXTURES), "--baseline", str(baseline)]) == 0


def test_cli_list_rules(capsys):
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for _, rule in FIXTURE_CASES:
        assert rule in out


def test_cli_missing_path_is_usage_error(capsys):
    assert analysis_main(["definitely/not/a/path"]) == 2


# ---------------------------------------------------------------------------
# @audited_solver contract
# ---------------------------------------------------------------------------


def test_audited_solver_attaches_property_report():
    from repro.core import oef
    from repro.core.properties import AUDITED_SOLVERS

    W = np.array([[1.0, 2.0], [1.0, 4.0]])
    m = np.array([4.0, 4.0])
    alloc = oef.solve_coop(W, m, audit=True)
    report = alloc.meta["audit"]
    assert report["envy_free"] and report["sharing_incentive"]
    assert "repro.core.oef.solve_coop" in AUDITED_SOLVERS
    assert getattr(oef.solve_coop, "__audited_solver__", False)


def test_audited_solver_off_by_default():
    from repro.core import oef

    W = np.array([[1.0, 2.0], [1.0, 4.0]])
    m = np.array([4.0, 4.0])
    assert "audit" not in oef.solve_noncoop(W, m).meta


def test_audited_solver_env_toggle(monkeypatch):
    from repro.core import baselines

    monkeypatch.setenv("REPRO_AUDIT", "1")
    W = np.array([[1.0, 2.0], [1.0, 4.0]])
    m = np.array([4.0, 4.0])
    alloc = baselines.solve_maxmin(W, m)
    assert "audit" in alloc.meta
