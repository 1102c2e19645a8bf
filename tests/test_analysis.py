"""Tests for repro.analysis — Pass A auditor, Pass B lint, CLI contract.

Default tier: every rule's known-bad fixture must flag and its known-good
twin must pass, the gather-free + donation audits run end-to-end on one
dense-KV and one MLA arch, and the CLI exit-code contract holds
(``--break-invariant RULE`` → non-zero with that rule id).  The
full-registry audit is slow-marked (CI runs it as the dedicated
``analysis`` job via ``python -m repro.analysis --all``).
"""
from __future__ import annotations

import json

import pytest

from repro.analysis import lint as lint_mod
from repro.analysis import tracekeys
from repro.analysis.__main__ import main as cli_main
from repro.analysis.fixtures import AUDIT_FIXTURES
from repro.analysis.rules import ALL_RULES, AUDIT_RULES, LINT_RULES
from repro.configs.registry import list_archs

DENSE_ARCH = "internlm2-1.8b"
MLA_ARCH = "minicpm3-4b"


# ---------------------------------------------------------------- rules ---
def test_every_rule_registered_with_fixture():
    assert set(ALL_RULES) == set(AUDIT_RULES) | set(LINT_RULES)
    for rule in LINT_RULES.values():
        assert rule.bad_fixture and rule.good_fixture, rule.id
    assert set(AUDIT_FIXTURES) == set(AUDIT_RULES)


@pytest.mark.parametrize("rule_id", sorted(LINT_RULES))
def test_lint_rule_flags_bad_and_passes_good(rule_id):
    rule = LINT_RULES[rule_id]
    bad = lint_mod.lint_source(rule.bad_fixture, f"{rule_id}:bad")
    good = lint_mod.lint_source(rule.good_fixture, f"{rule_id}:good")
    assert any(f.rule == rule_id for f in bad), f"{rule_id} is blind"
    assert not any(f.rule == rule_id for f in good), f"{rule_id} false-positives"


@pytest.mark.parametrize("rule_id", sorted(AUDIT_FIXTURES))
def test_audit_rule_flags_bad_and_passes_good(rule_id):
    bad_fn, good_fn = AUDIT_FIXTURES[rule_id]
    bad, good = bad_fn(), good_fn()
    assert any(f.rule == rule_id for f in bad), f"{rule_id} is blind"
    assert good == [], f"{rule_id} false-positives: {[f.format() for f in good]}"


# ----------------------------------------------------------- trace keys ---
def test_horizon_bucket_grid_matches_engine_rule():
    # doubles from 1, capacity always the final bucket
    assert tracekeys.horizon_bucket_grid(16, 4) == [1, 2, 4]
    assert tracekeys.horizon_bucket_grid(24, 4) == [1, 2, 4, 6]
    assert tracekeys.horizon_bucket_grid(4, 4) == [1]


@pytest.mark.parametrize("read_path,want", [
    ("streamed", [1, 2, 4, 6]), ("gathered", [1, 2, 4, 6]), ("pallas", [6]),
])
def test_horizon_bucket_grid_by_read(read_path, want):
    # the kernel skips blocks past each slot's context: one full bucket
    assert tracekeys.horizon_bucket_grid(24, 4, read_path) == want


def test_trace_key_space_and_bound():
    keys = tracekeys.trace_key_space(paged=True, max_seq=16, block_size=4)
    assert keys == {(k, b) for k in ("fused", "decode") for b in (1, 2, 4)}
    assert tracekeys.compile_bound(paged=True, grid=[1, 2, 4]) == {
        "fused": 3, "decode": 3,
    }
    assert tracekeys.trace_key_space(paged=False) == {
        ("fused", None), ("decode", None),
    }


def test_format_trace_key_diff_shows_extra_keys():
    expected = {("fused", 1), ("decode", 1)}
    seen = {("fused", 1), ("fused", 8)}
    txt = tracekeys.format_trace_key_diff(expected, seen, {"fused": 2})
    assert "EXTRA" in txt and "bucket=8" in txt and "fused=2" in txt


# ------------------------------------------------- end-to-end arch audit --
@pytest.mark.parametrize("arch", [DENSE_ARCH, MLA_ARCH])
def test_audit_arch_gather_free_and_donated(arch):
    from repro.analysis.audit import audit_arch

    findings = audit_arch(arch, tier="default", compile_donation=True)
    assert findings == [], "\n".join(f.format() for f in findings)


# ------------------------------------------------------------------ CLI ---
def test_cli_lint_repo_clean(capsys):
    assert cli_main(["--lint"]) == 0
    out = capsys.readouterr().out
    assert "ok=True" in out


def test_cli_self_check_green():
    assert cli_main(["--self-check"]) == 0


@pytest.mark.parametrize("rule_id", sorted(ALL_RULES))
def test_cli_break_invariant_nonzero_with_rule_id(rule_id, capsys):
    rc = cli_main(["--break-invariant", rule_id])
    out = capsys.readouterr().out
    assert rc != 0, f"{rule_id}: breaking the invariant must fail the gate"
    assert rule_id in out


def test_cli_json_report(tmp_path, capsys):
    path = tmp_path / "report.json"
    assert cli_main(["--self-check", "--json", str(path)]) == 0
    capsys.readouterr()
    d = json.loads(path.read_text())
    assert d["ok"] is True
    assert set(d["self_check"]) == set(ALL_RULES)


def test_cli_unknown_rule_errors():
    with pytest.raises(SystemExit):
        cli_main(["--break-invariant", "NO-SUCH-RULE"])


# ------------------------------------------------------------ full gate ---
@pytest.mark.slow
def test_cli_all_full_registry():
    # the CI `analysis` job: audit every registry arch + lint + self-check
    assert cli_main(["--all"]) == 0


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(set(list_archs()) - {DENSE_ARCH, MLA_ARCH}))
def test_audit_arch_rest_of_registry(arch):
    from repro.analysis.audit import audit_arch

    findings = audit_arch(arch, tier="full", compile_donation=True)
    assert findings == [], "\n".join(f.format() for f in findings)
