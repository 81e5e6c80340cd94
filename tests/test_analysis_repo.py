"""``dptpu check`` over the repo itself — the tier-1 CI gate (ISSUE 12).

Locks, per the acceptance criteria:

* the repo lints CLEAN: zero unsuppressed findings, every suppression
  carries a reason, and the committed ANALYSIS.json baseline agrees;
* the HLO budget gates hold: the four representative configs compile
  to exactly the committed HLO_BUDGETS.json and reproduce the analytic
  r06/COMMBENCH collective byte formulas;
* seeded regressions FAIL the check with the locked actionable
  message — a knob-contract violation (raw environ read) and a
  collective-budget change (tampered table) each produce a finding
  naming the rule/config, the location, and the remediation;
* the exit-code contract: 0 clean / 1 findings, via the real
  ``python -m dptpu.analysis`` entry.

The four compiles are TinyDense-sized (the tests/test_hierarchy.py
precedent) and cached module-wide — tier-1 pays them once.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from dptpu.analysis.lint import lint_repo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def repo_lint():
    findings, suppressions, n_files = lint_repo(ROOT)
    return findings, suppressions, n_files


@pytest.fixture(scope="module")
def computed_budgets(eight_devices):
    from dptpu.analysis.hlo_budget import compute_budgets

    return compute_budgets()


# ------------------------------------------------------- the clean gate


def test_repo_lints_clean(repo_lint):
    findings, _, n_files = repo_lint
    assert n_files > 100  # the whole dptpu/ + scripts/ tree, not a stub
    assert findings == [], "unsuppressed findings:\n" + "\n".join(
        f.format() for f in findings
    )


def test_every_suppression_carries_a_reason(repo_lint):
    _, suppressions, _ = repo_lint
    assert suppressions, "the repo documents its waivers via pragmas"
    for s in suppressions:
        assert s.reason.strip(), f"reasonless suppression at " \
                                 f"{s.path}:{s.line}"


def test_committed_analysis_baseline_agrees(repo_lint):
    with open(os.path.join(ROOT, "ANALYSIS.json"), encoding="utf-8") as f:
        baseline = json.load(f)
    assert baseline["ok"] is True
    assert baseline["lint"]["findings"] == []
    # the committed suppression census matches the live tree
    findings, suppressions, _ = repo_lint
    live = {(s.path, s.rule) for s in suppressions}
    committed = {(s["path"], s["rule"])
                 for s in baseline["lint"]["suppressions"]}
    assert live == committed, (
        "suppressions changed — regenerate the baseline with "
        "`dptpu check --json ANALYSIS.json`"
    )
    assert baseline["hlo"]["ok"] is True
    assert "provenance" in baseline  # host-stamped like every artifact


def test_hlo_budget_gate_holds(computed_budgets):
    from dptpu.analysis.hlo_budget import check_hlo_budgets

    violations, computed = check_hlo_budgets(
        ROOT, computed=computed_budgets
    )
    assert violations == [], "\n".join(v.format() for v in violations)
    # the committed table IS the compiled truth, byte for byte
    with open(os.path.join(ROOT, "HLO_BUDGETS.json"),
              encoding="utf-8") as f:
        committed = json.load(f)
    assert committed["configs"] == computed["configs"]


def test_budget_table_reproduces_analytic_formulas(computed_budgets):
    """The committed numbers re-derive from the r06/COMMBENCH formulas
    (tests/test_hierarchy.py's locks, restated against the table)."""
    g = computed_budgets["model"]["grad_bytes"]
    p = computed_budgets["model"]["pmean_bytes"]
    n = computed_budgets["geometry"]["devices"]
    s = computed_budgets["geometry"]["slices"]
    inner = computed_budgets["geometry"]["inner"]
    cfg = computed_budgets["configs"]
    ddp = cfg["ddp"]["per_chip"]
    assert ddp["reduce-scatter"] == 0 and ddp["all-gather"] == 0
    want = 2 * (n - 1) / n * (g + p)
    assert abs(ddp["all-reduce"] - want) / want < 0.02
    assert cfg["accum"]["per_chip"] == ddp  # ONE reduction per update
    z = cfg["zero1"]["per_chip"]["total"]
    assert abs(z - ddp["total"]) / ddp["total"] < 0.001
    link = cfg["slices"]["by_link"]
    assert link["ici"]["all-reduce"] == 0
    assert link["dcn"]["reduce-scatter"] == 0
    assert link["dcn"]["all-gather"] == 0
    want_ici = 2 * (inner - 1) / inner * g
    want_dcn = 2 * (s - 1) / s * g / inner + 2 * (n - 1) / n * p
    assert abs(link["ici"]["total"] - want_ici) / want_ici < 0.02
    assert abs(link["dcn"]["total"] - want_dcn) / want_dcn < 0.02
    for name, row in cfg.items():
        assert row["f64_shapes"] == 0
        if name == "serve_quant":
            # an inference forward donates nothing; its row gates the
            # REQUESTED matmul dtypes instead — every dot bf16, s8
            # parameters present, no silent fp32 fallback
            assert row["s8_params"] >= 1
            assert row["dots"].get("bf16", 0) >= 1
            assert not row["dots"].get("f32", 0) \
                and not row["dots"].get("f64", 0)
            continue
        assert row["alias_entries"] >= \
            computed_budgets["model"]["param_leaves"]


# --------------------------------------------------- seeded regressions


def test_seeded_knob_violation_fails_actionably(tmp_path):
    """A raw environ read of a DPTPU knob must fail the check with the
    locked message: rule name, file:line, pragma syntax."""
    pkg = tmp_path / "dptpu"
    pkg.mkdir()
    bad = pkg / "newmod.py"
    bad.write_text(
        'import os\nv = os.environ.get("DPTPU_ACCUM", "1")\n'
    )
    findings, _, _ = lint_repo(str(tmp_path))
    assert len(findings) == 1
    msg = findings[0].format()
    assert "knob-contract" in msg
    assert "dptpu/newmod.py:2" in msg
    assert "# dptpu: allow-knob-contract(" in msg
    assert "envknob" in msg


def test_seeded_budget_change_fails_actionably(computed_budgets):
    """A collective-budget drift (here: one DCN byte) must fail the
    gate naming the config, both values, and the re-commit path."""
    from dptpu.analysis.hlo_budget import check_hlo_budgets

    tampered = copy.deepcopy(computed_budgets)
    row = tampered["configs"]["slices"]["by_link"]["dcn"]
    row["all-reduce"] += 1
    row["total"] += 1
    violations, _ = check_hlo_budgets(
        ROOT, budgets=tampered, computed=computed_budgets
    )
    assert len(violations) == 1
    msg = violations[0].format()
    assert "slices" in msg and "by_link" in msg
    assert "--update-hlo-budgets" in msg
    # ...and an instruction-count change trips the same gate
    tampered = copy.deepcopy(computed_budgets)
    tampered["configs"]["ddp"]["collective_instructions"][
        "all-reduce"] -= 1
    violations, _ = check_hlo_budgets(
        ROOT, budgets=tampered, computed=computed_budgets
    )
    assert any("collective_instructions" in v.format()
               for v in violations)


# ---------------------------------------------------- exit-code contract


def _run_check(*args):
    return subprocess.run(
        [sys.executable, "-m", "dptpu.analysis", *args],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )


def test_exit_code_contract_clean_repo():
    proc = _run_check("--no-hlo", "--root", ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_exit_code_contract_findings(tmp_path):
    pkg = tmp_path / "dptpu"
    pkg.mkdir()
    (pkg / "newmod.py").write_text(
        'import os\nv = os.environ.get("DPTPU_ACCUM", "1")\n'
    )
    proc = _run_check("--no-hlo", "--root", str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "knob-contract" in proc.stdout
    assert "NOT CLEAN" in proc.stdout


def test_update_budgets_with_no_hlo_is_refused():
    """Committing a table the gates never validated must be a usage
    error (argparse exit 2), never a silent 'clean'."""
    proc = _run_check("--update-hlo-budgets", "--no-hlo", "--root", ROOT)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "drop --no-hlo" in proc.stderr


def test_exit_code_contract_wrong_root_is_usage_error(tmp_path):
    """A mis-set --root (no dptpu/ or scripts/ underneath) must exit 2,
    never report a zero-file scan as 'clean'."""
    proc = _run_check("--no-hlo", "--root", str(tmp_path))
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "wrong directory" in proc.stderr


def test_no_hlo_run_never_imports_jax():
    """The lint half's worker-safe contract, enforced for real: a
    --no-hlo run (including its provenance stamp) must finish with jax
    absent from sys.modules."""
    proc = subprocess.run(
        [sys.executable, "-c",
         # drop jax if anything preloaded it, so that any import
         # ATTEMPT during the lint re-registers it visibly
         "import sys\n"
         "sys.modules.pop('jax', None)\n"
         "from dptpu.analysis.cli import main_check\n"
         "rc = main_check(['--no-hlo', '--quiet'])\n"
         "assert 'jax' not in sys.modules, 'lint run imported jax'\n"
         "sys.exit(rc)"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items()},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
