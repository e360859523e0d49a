"""jaxlint tests: the fixture corpus (positive AND negative per rule),
suppression semantics, fingerprint stability, baseline diffing, CLI exit
codes, and the repo-wide gate (deepspeed_tpu/ + tools/ lint clean
against the committed baseline, under the 3 s CI budget).

Cross-file behavior (the project graph, --diff mode, --explain, the
summary cache) lives in test_jaxlint_v2.py.

Everything here is AST-only — no jax import, so this file is one of the
fastest in the suite.
"""

import json
import os

import pytest

from tools.jaxlint import (
    ALL_CODES,
    HOT_LOOPS,
    RULES,
    analyze_file,
    analyze_paths,
    analyze_source,
    count_findings,
    diff_against_baseline,
    load_baseline,
    write_baseline,
)
from tools.jaxlint.analyzer import _FileIndex
from tools.jaxlint.cli import main as jaxlint_main

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "jaxlint_fixtures")
BASELINE = os.path.join(REPO_ROOT, "jaxlint_baseline.json")

# fixture file -> (rule code, expected positive-finding count)
POSITIVES = {
    "jl001_pos.py": ("JL001", 4),
    "jl002_pos.py": ("JL002", 5),
    "jl003_pos.py": ("JL003", 2),
    "jl004_pos.py": ("JL004", 2),
    "jl005_pos.py": ("JL005", 2),
    "fp16_jl006_pos.py": ("JL006", 2),
    "jl007_pos.py": ("JL007", 3),
    "jl008_pos.py": ("JL008", 2),
    "jl009_pos.py": ("JL009", 4),
    "jl010_pos.py": ("JL010", 3),
    "jl011_pos.py": ("JL011", 2),
}
NEGATIVES = {
    "JL001": "jl001_neg.py",
    "JL002": "jl002_neg.py",
    "JL003": "jl003_neg.py",
    "JL004": "jl004_neg.py",
    "JL005": "jl005_neg.py",
    "JL006": "fp16_jl006_neg.py",
    "JL007": "jl007_neg.py",
    "JL008": "jl008_neg.py",
    "JL009": "jl009_neg.py",
    "JL010": "jl010_neg.py",
    "JL011": "jl011_neg.py",
}


def _lint(name):
    return analyze_file(os.path.join(FIXTURES, name), root=REPO_ROOT)


# -- rule corpus --------------------------------------------------------------

@pytest.mark.parametrize("name,code,count",
                         [(n, c, k) for n, (c, k) in POSITIVES.items()])
def test_positive_fixture_flags_its_rule(name, code, count):
    findings = _lint(name)
    assert [f.code for f in findings] == [code] * count, \
        [f.render() for f in findings]


@pytest.mark.parametrize("code,name", sorted(NEGATIVES.items()))
def test_negative_fixture_is_clean(code, name):
    findings = _lint(name)
    assert findings == [], [f.render() for f in findings]


def test_every_rule_has_a_fixture_pair():
    covered = {code for code, _ in POSITIVES.values()}
    assert covered == set(ALL_CODES) == set(NEGATIVES)
    assert set(RULES) == set(ALL_CODES)


def test_findings_carry_symbol_and_text():
    by_symbol = {f.symbol for f in _lint("jl001_pos.py")}
    assert "relu_branch" in by_symbol and "halve_until_small" in by_symbol
    for f in _lint("jl001_pos.py"):
        assert f.text  # the anchor line is embedded for fingerprinting


def test_jl011_registry_is_single_source_of_truth():
    """JL011(c): a dict assigned to *_PARTITION_RULES is canonical for
    the paths it registers — disagreeing literals are flagged even when
    they sort before the rule table, with a registry-specific message."""
    findings = _lint("jl011_registry_pos.py")
    assert [f.code for f in findings] == ["JL011"] * 2, \
        [f.render() for f in findings]
    for f in findings:
        assert "single source of truth" in f.message, f.render()
    # the ad-hoc literals are flagged, never the rule table itself
    assert all("PARTITION_RULES" not in f.text for f in findings)


def test_jl011_registry_negative_is_clean():
    findings = _lint("jl011_registry_neg.py")
    assert findings == [], [f.render() for f in findings]


def test_jl006_only_fires_on_fp16_paths():
    src = "import jax.numpy as jnp\n\ndef f(shape):\n    return jnp.zeros(shape)\n"
    assert analyze_source(src, rel_path="deepspeed_tpu/runtime/fp16/x.py")
    assert not analyze_source(src, rel_path="deepspeed_tpu/runtime/utils.py")


def test_registered_hot_loops_exist_and_resolve():
    """The HOT_LOOPS registry must track the real engines — a rename
    there would silently turn JL002 off for the hot path."""
    for suffix, qual in HOT_LOOPS:
        path = os.path.join(REPO_ROOT, suffix)
        assert os.path.exists(path), f"HOT_LOOPS entry points nowhere: {suffix}"
        with open(path, "r", encoding="utf-8") as fh:
            index = _FileIndex(path, suffix, fh.read())
        hot = {index.qualname.get(n, n.name) for n in index.hot_defs()}
        assert qual in hot, f"{qual} not found in {suffix}"


def test_syntax_error_reports_jl000(tmp_path):
    broken = tmp_path / "broken.py"
    broken.write_text("def broken(:\n")
    result = analyze_file(str(broken), root=str(tmp_path))
    assert [f.code for f in result] == ["JL000"]


# -- suppressions -------------------------------------------------------------

def test_suppression_same_line_and_line_above():
    findings = _lint("suppressed.py")
    assert [f.symbol for f in findings] == ["wrong_code_still_flagged"]
    assert findings[0].code == "JL001"


def test_suppression_requires_matching_code():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:  # jaxlint: disable=JL001\n"
        "        return x\n"
        "    return -x\n"
    )
    assert analyze_source(src, rel_path="a.py") == []
    assert analyze_source(src.replace("JL001", "JL003"), rel_path="a.py")


# -- fingerprints and baseline ------------------------------------------------

def test_fingerprint_stable_under_line_shift():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    before = analyze_source(src, rel_path="m.py")
    after = analyze_source("# moved\n\n\n" + src, rel_path="m.py")
    assert [f.fingerprint() for f in before] == \
        [f.fingerprint() for f in after]
    assert before[0].line != after[0].line  # the line DID shift


def test_baseline_round_trip_and_diff(tmp_path):
    findings = _lint("jl001_pos.py")
    path = tmp_path / "baseline.json"
    write_baseline(str(path), findings)
    counts = load_baseline(str(path))
    assert counts == count_findings(findings)

    # everything baselined: nothing new, nothing stale
    new, stale = diff_against_baseline(findings, counts)
    assert new == [] and stale == []

    # an extra finding in a different file IS new
    extra = _lint("jl003_pos.py")
    new, stale = diff_against_baseline(findings + extra, counts)
    assert {f.code for f in new} == {"JL003"} and len(new) == 2

    # a fixed finding shows up as stale, never blocks
    new, stale = diff_against_baseline(findings[1:], counts)
    assert new == [] and len(stale) == 1


def test_baseline_counts_gate_duplicates():
    findings = _lint("jl001_pos.py")
    fp = findings[0].fingerprint()
    # baseline allows ONE occurrence of the first fingerprint only
    new, _ = diff_against_baseline(findings, {fp: 1})
    assert len(new) == len(findings) - 1
    assert all(f.fingerprint() != fp for f in new)


def test_baseline_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"findings": {"x": 0}, "version": 1}))
    with pytest.raises(ValueError):
        load_baseline(str(bad))
    bad.write_text(json.dumps({"version": 99, "findings": {}}))
    with pytest.raises(ValueError):
        load_baseline(str(bad))
    bad.write_text(json.dumps([1, 2]))
    with pytest.raises(ValueError):
        load_baseline(str(bad))


# -- CLI ----------------------------------------------------------------------

def test_cli_exit_codes(tmp_path, capsys):
    pos = os.path.join(FIXTURES, "jl001_pos.py")
    neg = os.path.join(FIXTURES, "jl001_neg.py")
    assert jaxlint_main([neg, "--root", REPO_ROOT]) == 0
    assert jaxlint_main([pos, "--root", REPO_ROOT]) == 1
    assert jaxlint_main(["/no/such/path"]) == 2
    assert jaxlint_main([pos, "--select", "JL999"]) == 2
    capsys.readouterr()


def test_cli_baseline_workflow(tmp_path, capsys):
    pos = os.path.join(FIXTURES, "jl001_pos.py")
    baseline = str(tmp_path / "b.json")
    # --write-baseline grandfathers the current findings...
    assert jaxlint_main([pos, "--root", REPO_ROOT, "--baseline", baseline,
                         "--write-baseline"]) == 0
    # ...so the same run now passes...
    assert jaxlint_main([pos, "--root", REPO_ROOT,
                         "--baseline", baseline]) == 0
    # ...but a seeded NEW finding still fails it
    seeded = tmp_path / "seeded.py"
    seeded.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")
    assert jaxlint_main([pos, str(seeded), "--root", REPO_ROOT,
                         "--baseline", baseline]) == 1
    out = capsys.readouterr().out
    assert "seeded.py" in out and "JL001" in out


def test_cli_select_filters_rules(capsys):
    pos = os.path.join(FIXTURES, "jl003_pos.py")
    assert jaxlint_main([pos, "--root", REPO_ROOT,
                         "--select", "JL001"]) == 0  # only JL003 in the file
    assert jaxlint_main([pos, "--root", REPO_ROOT,
                         "--select", "JL003"]) == 1
    capsys.readouterr()


def test_cli_json_format(capsys):
    pos = os.path.join(FIXTURES, "jl004_pos.py")
    assert jaxlint_main([pos, "--root", REPO_ROOT, "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["total_findings"] == 2
    assert {f["code"] for f in payload["new"]} == {"JL004"}


# -- the repo-wide gate -------------------------------------------------------

def test_repo_lints_clean_against_committed_baseline():
    """The CI gate, as a test: deepspeed_tpu/ + tools/ produce no
    findings beyond the committed baseline. (No wall-clock budget here: a
    stopwatch fails under the driver's six workers and finds nothing.)"""
    findings, n_files = analyze_paths(
        [os.path.join(REPO_ROOT, "deepspeed_tpu"),
         os.path.join(REPO_ROOT, "tools")],
        root=REPO_ROOT)
    baseline = load_baseline(BASELINE)
    new, _stale = diff_against_baseline(findings, baseline)
    assert new == [], "new jaxlint findings:\n" + "\n".join(
        f.render() for f in new)
    assert n_files > 100  # the walk really covered the package


def test_ops_and_fp16_are_lint_clean_with_no_baseline():
    """Drive-by guarantee: these two subtrees carry ZERO baselined debt —
    every finding there is fixed or suppressed inline with a reason."""
    for sub in ("deepspeed_tpu/ops", "deepspeed_tpu/runtime/fp16"):
        findings, n_files = analyze_paths(
            [os.path.join(REPO_ROOT, sub)], root=REPO_ROOT)
        assert n_files > 0
        assert findings == [], f"{sub}:\n" + "\n".join(
            f.render() for f in findings)
