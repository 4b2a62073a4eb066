"""The whole-program analyzer (ISSUE 14): cross-module registry
index, audit mode, SARIF output, and the wall-budget regression.

The per-rule fixture corpus rides tests/test_staticcheck.py; this
module covers what only the TWO-PASS analysis can see — the
cross-module fixture trees under tests/staticcheck_fixtures/xmodule/
stand up miniature wire/pb, metrics/exposition/golden, and
config/perfgate/tests registries and assert the exact cross-file
findings (bad) and a clean bill (good)."""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.staticcheck.core import (  # noqa: E402
    check_paths,
    load_pragma_budget,
)

XMODULE = REPO / "tests" / "staticcheck_fixtures" / "xmodule"


def _findings(root):
    found, _n = check_paths([root], root)
    return {(f.rule, f.path, f.line) for f in found}


def test_xmodule_bad_tree_exact_cross_module_findings():
    """Each defect lives in a DIFFERENT file from the registry that
    convicts it: flag vs fingerprint/tests, counter vs snapshot,
    family vs golden, kind vs pb adapter."""
    assert _findings(XMODULE / "bad") == {
        # xb_turbo is read+pinned but missing from tools/perfgate.py's
        # fingerprint dict
        ("ARM001", "pkg/config.py", 12),
        # xb_nitro is read+fingerprinted but never pinned in tests/
        ("ARM001", "pkg/config.py", 13),
        # xb_gears (int arm) is read+fingerprinted but pins only ONE
        # distinct value in tests/ (the baseline; no fast-arm pin)
        ("ARM001", "pkg/config.py", 14),
        # xb_lost_total is incremented in pkg/engine.py but never
        # reaches pkg/metrics.py's snapshot()
        ("SCHEMA001", "pkg/metrics.py", 16),
        # the golden's xb_ghost_total is emitted by no exposition
        ("SCHEMA001", "pkg/obs.py", 1),
        # xb_stray_total is emitted but absent from the golden
        ("SCHEMA001", "pkg/obs.py", 12),
        # _KIND_TWO has no slot in the import-stem-paired pb adapter
        ("WIRE001", "pkg/transport/wiremsg.py", 5),
    }


def test_xmodule_good_tree_is_clean():
    assert _findings(XMODULE / "good") == set()


def test_callgraph_bad_tree_exact_cross_module_findings():
    """Pass 3 (ISSUE 17): each conviction needs a call edge into
    ANOTHER file — the guarded class, the blocking helper and the
    entropy source all live one module away from the code that
    misuses them."""
    assert _findings(XMODULE / "callgraph_bad") == {
        # clock.wall's direct wall-clock read (per-file DET001)...
        ("DET001", "pkg/protocol/clock.py", 5),
        # ...and where its return value LANDS two files away
        ("DET007", "pkg/protocol/engine.py", 15),
        # engine calls state.Table._get_locked() holding no lock
        ("CONC003", "pkg/protocol/engine.py", 10),
        # conn.handle_frame reaches helpers.slow_write's fsync;
        # the finding sits at the BLOCKING line, not the handler
        ("CONC004", "pkg/transport/helpers.py", 5),
    }


def test_callgraph_good_tree_is_clean():
    assert _findings(XMODULE / "callgraph_good") == set()


def test_callgraph_findings_carry_their_evidence_chain():
    """CONC004's related tuple is the hop-by-hop call path from the
    handler entry down to the blocking call — the debuggability
    contract the SARIF relatedLocations ride on."""
    root = XMODULE / "callgraph_bad"
    found, _n = check_paths([root], root)
    by_rule = {f.rule: f for f in found}
    chain = by_rule["CONC004"].related
    assert [(p, ln) for p, ln, _note in chain] == [
        ("pkg/transport/conn.py", 11),
        ("pkg/transport/helpers.py", 4),
    ]
    assert "handle_frame" in chain[0][2]
    # CONC003/DET007 point back at the defining/origin site
    assert by_rule["CONC003"].related[0][:2] == (
        "pkg/protocol/state.py",
        12,
    )
    assert by_rule["DET007"].related[0][:2] == (
        "pkg/protocol/clock.py",
        4,
    )


def test_xmodule_good_breaks_when_fingerprint_key_removed(tmp_path):
    """The index really reads the OTHER file: deleting the good
    tree's fingerprint key manufactures the ARM001 finding."""
    import shutil

    root = tmp_path / "tree"
    shutil.copytree(XMODULE / "good", root)
    pg = root / "tools" / "perfgate.py"
    pg.write_text(
        pg.read_text(encoding="utf-8").replace(
            '"xg_turbo": bool(cfg.xg_turbo),', ""
        ),
        encoding="utf-8",
    )
    rules = {f[0] for f in _findings(root)}
    assert rules == {"ARM001"}


# ---------------------------------------------------------------------------
# audit mode
# ---------------------------------------------------------------------------


def _write_plane_file(tmp_path, body):
    mod = tmp_path / "protocol" / "mod.py"
    mod.parent.mkdir(exist_ok=True)
    mod.write_text(body, encoding="utf-8")
    return mod


# assembled from pieces so the tree-wide audit of THIS file's source
# never sees a pragma-shaped line of its own
_P = "# staticcheck" + ": "
AUDIT_SRC = (
    "import time\n"
    "\n"
    "\n"
    "def f():\n"
    "    return time.time()  " + _P + "allow[DET001] sanctioned\n"
    "x = 1  " + _P + "allow[DET002] nothing ever fired here\n"
)


def test_audit_reports_stale_pragma_and_keeps_live_one(tmp_path):
    _write_plane_file(tmp_path, AUDIT_SRC)
    findings, _n = check_paths(
        [tmp_path], tmp_path, audit=True, pragma_budget=None
    )
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # the DET001 pragma suppresses a real finding: not stale; the
    # DET002 pragma suppresses nothing: PRAGMA002 at its exact line
    assert "DET001" not in by_rule
    stale = by_rule.pop("PRAGMA002")
    assert [(f.line) for f in stale] == [6]
    assert "allow-file" not in stale[0].message
    assert not by_rule  # nothing else


def test_audit_budget_gates_pragma_growth(tmp_path):
    _write_plane_file(tmp_path, AUDIT_SRC)
    over, _n = check_paths(
        [tmp_path], tmp_path, audit=True, pragma_budget=1
    )
    assert any(f.rule == "PRAGMA003" for f in over)
    under, _n = check_paths(
        [tmp_path], tmp_path, audit=True, pragma_budget=2
    )
    assert not any(f.rule == "PRAGMA003" for f in under)


def test_tree_pragma_budget_matches_population():
    """The committed budget is EXACT: adding a pragma anywhere in the
    gated tree must force a deliberate budget bump in review."""
    budget = load_pragma_budget()
    assert budget is not None
    targets = [REPO / p for p in ("cleisthenes_tpu", "tools", "tests")]
    findings, _n = check_paths(
        targets, REPO, audit=True, pragma_budget=budget
    )
    assert [f.render() for f in findings] == []
    over, _n = check_paths(
        targets, REPO, audit=True, pragma_budget=budget - 1
    )
    assert any(f.rule == "PRAGMA003" for f in over)


# ---------------------------------------------------------------------------
# CLI: SARIF output + the wall-budget regression
# ---------------------------------------------------------------------------


def _run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_lone_real_file_scan_has_no_standing_to_convict_absence():
    """Single-file runs of the real registry modules must stay clean:
    'never incremented' / 'never read' are claims about consumers the
    scan cannot see (self-contained fixtures keep the full rule set —
    tests/test_staticcheck.py proves they still gate)."""
    for rel in (
        "cleisthenes_tpu/utils/metrics.py",
        "cleisthenes_tpu/config.py",
        "cleisthenes_tpu/protocol/acs.py",
    ):
        findings, _n = check_paths([REPO / rel], REPO)
        assert [f.render() for f in findings] == [], rel


def test_rules_subset_does_not_fake_stale_pragmas():
    """--rules narrows the REPORT, not the audit's evidence: pragma
    staleness is judged against every rule's raw findings, so a
    DET001-only run must not declare the WIRE001/DET004 pragmas
    stale."""
    proc = _run_cli(
        "cleisthenes_tpu",
        "tools",
        "tests",
        "--rules",
        "DET001",
        "--audit-pragmas",
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_fingerprint_registry_prefers_real_perfgate():
    """Fingerprint-shaped dict literals in tests must not mask a key
    dropped from the real perfgate fingerprint: with a perfgate.py in
    the scan, only its keys count."""
    from tools.staticcheck.core import _load_contexts
    from tools.staticcheck.program import build_index

    ctxs, _pf, _n = _load_contexts(
        [REPO / p for p in ("cleisthenes_tpu", "tools", "tests")], REPO
    )
    index = build_index(ctxs, REPO)
    # every declared arm flag keys the real fingerprint...
    from cleisthenes_tpu.config import ARM_FLAGS

    assert set(ARM_FLAGS) <= index.fingerprint_keys
    # ...and test_obs's mini record dicts were not unioned in
    assert "k" not in index.fingerprint_keys


def test_sarif_output_is_annotatable():
    proc = _run_cli(
        "tests/staticcheck_fixtures/transport/wire001_bad.py",
        "--format",
        "sarif",
        "--no-baseline",
    )
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "cleisthenes-staticcheck"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"WIRE001", "SCHEMA001", "ARM001", "VERIFY001"} <= rule_ids
    results = run["results"]
    locs = {
        (
            r["ruleId"],
            r["locations"][0]["physicalLocation"]["artifactLocation"][
                "uri"
            ],
            r["locations"][0]["physicalLocation"]["region"]["startLine"],
        )
        for r in results
    }
    rel = "tests/staticcheck_fixtures/transport/wire001_bad.py"
    assert locs == {
        ("WIRE001", rel, 8),
        ("WIRE001", rel, 9),
        ("WIRE001", rel, 10),
    }


def test_sarif_carries_related_locations_for_call_chains():
    """A pass-3 finding's SARIF result embeds the full call chain as
    relatedLocations, so the report alone shows WHY the sink is
    reachable."""
    proc = _run_cli(
        "tests/staticcheck_fixtures/transport/conc004_bad.py",
        "--format",
        "sarif",
        "--no-baseline",
    )
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    results = doc["runs"][0]["results"]
    conc004 = [r for r in results if r["ruleId"] == "CONC004"]
    assert conc004
    for r in conc004:
        rels = r["relatedLocations"]
        assert len(rels) >= 2  # >=1 hop + the containing function
        for rel_loc in rels:
            phys = rel_loc["physicalLocation"]
            assert phys["artifactLocation"]["uriBaseId"] == "SRCROOT"
            assert phys["region"]["startLine"] > 0
            assert rel_loc["message"]["text"]
    # the deepest chain walks serve_batch -> _relay -> _deep_relay
    deepest = max(conc004, key=lambda r: len(r["relatedLocations"]))
    notes = [x["message"]["text"] for x in deepest["relatedLocations"]]
    assert "serve_batch" in notes[0] and "_relay" in notes[0]
    assert "blocking call" in notes[-1]


def test_whole_program_pass_under_wall_budget():
    """The two-pass tree-wide run (the exact ci.sh stage-2 command)
    must stay far from being the slow CI stage: zero findings, and
    well under a minute on the tier-1 box (typically a few seconds)."""
    t0 = time.monotonic()
    proc = _run_cli(
        "cleisthenes_tpu", "tools", "tests", "--audit-pragmas"
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 60.0, f"staticcheck took {elapsed:.1f}s"
