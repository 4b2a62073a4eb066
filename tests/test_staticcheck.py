"""tools/staticcheck: the analyzer's own test coverage.

The fixture corpus under tests/staticcheck_fixtures/ carries
known-bad and known-good snippets per rule; bad lines are tagged
``# BAD:<RULE>`` and the tests assert the EXACT (rule, line) set the
analyzer reports — a finding on an untagged line or a missed tag both
fail.  Fixture paths reuse the analyzer's path-derived scoping
(protocol/ = determinism plane, transport/ = transport scope), so
scope resolution itself is under test too.
"""

from __future__ import annotations

import json
import pathlib
import re
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.staticcheck import (  # noqa: E402
    BASELINE_PATH,
    check_paths,
    load_baseline,
    registered_rules,
    split_baselined,
    write_baseline,
)
from tools.staticcheck.core import check_file  # noqa: E402

FIXTURES = REPO / "tests" / "staticcheck_fixtures"
_BAD_RE = re.compile(r"#\s*BAD:([A-Z0-9]+)")


def expected_findings(path: pathlib.Path):
    """{(rule, line)} from the fixture's # BAD:<RULE> tags."""
    out = set()
    for i, line in enumerate(
        path.read_text(encoding="utf-8").splitlines(), 1
    ):
        m = _BAD_RE.search(line)
        if m:
            out.add((m.group(1), i))
    return out


def reported_findings(path: pathlib.Path):
    return {(f.rule, f.line) for f in check_file(path, REPO)}


BAD_FIXTURES = [
    "protocol/det001_bad.py",
    # the observability plane does not relax DET001: raw perf_counter
    # in protocol code gates even with utils/trace.py landed (its
    # allow[DET001] pragma is confined to that one file)
    "protocol/det001_trace_bad.py",
    # ...and neither does the live telemetry plane: the sampler /
    # watchdog clocks are pragma'd in utils/timeseries.py and
    # utils/watchdog.py only — a hand-rolled sampler loop or stall
    # clock in protocol/ still gates
    "protocol/det001_obs_bad.py",
    "protocol/det002_bad.py",
    # the EchoBank surface (ISSUE 9): a hand-rolled receipt bank that
    # iterates sender/root sets in hash order still gates — the bank
    # exists precisely so no set order reaches the delivery plane
    "protocol/det002_echobank_bad.py",
    # the columnar seam (ISSUE 7): direct BatchCrypto verify/decode
    # from protocol/ outside hub.py gates, so the wave refactor can't
    # silently erode back to scalar dispatch
    "protocol/det003_bad.py",
    # the wave-router seam (ISSUE 10): per-frame serve_request /
    # handle_message dispatch from transport code still gates — the
    # router's one-dispatch-per-kind-per-wave discipline can't
    # silently erode back to one Python call chain per payload
    "transport/det004_bad.py",
    # the roster-version seam (ISSUE 12): epoch-scoped protocol code
    # reading the construction-time n/f/keys/membership still gates —
    # a fixed-roster read is correct right up until the first
    # RECONFIG crosses, then a silent fork
    "protocol/det005_bad.py",
    # the lane-frontier seam (ISSUE 20): lane-scoped protocol code
    # reading the bare primary-lane epoch/settled/committed frontier
    # still gates — a bare read silently pins lane 0's frontier the
    # moment a second lane exists
    "protocol/det005_lane_bad.py",
    # the egress wave-signer seam (ISSUE 13): per-frame envelope
    # encode+sign from a transport send path still gates — the
    # one-sign-pass-per-wave discipline can't silently erode back to
    # one encode + MAC per post
    "transport/det006_bad.py",
    # the wire registry (ISSUE 14): duplicate kind numbers, kinds no
    # parser accepts and kinds no encoder emits gate at the registry
    # declaration — the two-pass index works on a single file too
    "transport/wire001_bad.py",
    # ...and the pb-adapter side: duplicate extension tags, reserved
    # envelope numbers, orphaned tags
    "transport/pb001_bad.py",
    # the snapshot-schema registry (ISSUE 14): counters nothing
    # increments and counters that never reach snapshot() gate at the
    # declaration line
    "protocol/schema001_bad.py",
    # the arm registry (ISSUE 14): stale ARM_FLAGS entries (a removed
    # option still named there), dead arm flags and live flags the
    # perfgate fingerprint does not key on
    "protocol/arm001_bad.py",
    # the verify-before-dispatch taint walk (ISSUE 14): decoded frames
    # reaching a handler sink with no verify_wire* in between
    "transport/verify001_bad.py",
    "protocol/conc001_bad.py",
    "transport/conc002_bad.py",
    # the caller-holds-lock contract (ISSUE 17): *_locked callees
    # invoked without the callee class's declared lock — the
    # interprocedural gap CONC001's same-method scan cannot see
    "protocol/conc003_bad.py",
    # blocking calls one or more hops BELOW a handler (ISSUE 17):
    # CONC002 sees a clean handler body; the pass-3 reachability
    # walk convicts the helper's fsync/sleep/recv
    "transport/conc004_bad.py",
    # interprocedural entropy taint (ISSUE 17): DET001 convicts the
    # source line, DET007 convicts where the derived value LANDS in
    # plane state — one hop apart within a file here, cross-module
    # in the xmodule/callgraph_bad tree
    "protocol/det007_bad.py",
    "protocol/err001_bad.py",
    # the WAN stem rule (ISSUE 16): transport files named wan/wan_*
    # join the determinism plane, so raw random/wall-clock in a link
    # model gates — seeded WAN schedules must replay byte-identically
    "transport/wan_det001_bad.py",
]
GOOD_FIXTURES = [
    "protocol/det001_good.py",
    "protocol/det002_good.py",
    "protocol/det003_good.py",
    "transport/det004_good.py",
    "protocol/det005_good.py",
    "protocol/det005_lane_good.py",
    "transport/det006_good.py",
    "transport/wire001_good.py",
    "transport/pb001_good.py",
    "protocol/schema001_good.py",
    "protocol/arm001_good.py",
    "transport/verify001_good.py",
    "protocol/conc001_good.py",
    "transport/conc002_good.py",
    "protocol/conc003_good.py",
    "transport/conc004_good.py",
    "protocol/det007_good.py",
    "protocol/err001_good.py",
    "transport/wan_det001_good.py",
    "protocol/pragma_file_cases.py",
]


@pytest.mark.parametrize("rel", BAD_FIXTURES)
def test_known_bad_exact_locations(rel):
    path = FIXTURES / rel
    expected = expected_findings(path)
    assert expected, f"fixture {rel} has no # BAD tags"
    assert reported_findings(path) == expected


@pytest.mark.parametrize("rel", GOOD_FIXTURES)
def test_known_good_is_clean(rel):
    path = FIXTURES / rel
    assert reported_findings(path) == set()


def test_out_of_plane_paths_skip_plane_rules(tmp_path):
    # identical source, no protocol/core/ops in the path: DET rules
    # must not fire (the plane is path-defined)
    src = (FIXTURES / "protocol" / "det001_bad.py").read_text(
        encoding="utf-8"
    )
    out = tmp_path / "toolscratch" / "det001_elsewhere.py"
    out.parent.mkdir()
    out.write_text(src, encoding="utf-8")
    rules = {f.rule for f in check_file(out, tmp_path)}
    assert "DET001" not in rules


def test_pragma_suppression_and_missing_justification():
    path = FIXTURES / "protocol" / "pragma_cases.py"
    findings = check_file(path, REPO)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # the justified pragma suppressed its DET001; the bare pragma
    # suppressed nothing AND is itself reported
    assert len(by_rule.get("DET001", [])) == 1
    assert len(by_rule.get("PRAGMA001", [])) == 1
    det = by_rule["DET001"][0]
    bare = by_rule["PRAGMA001"][0]
    assert det.line == bare.line  # both point at the bare-pragma line
    assert "time.time" in det.message


def test_baseline_round_trip(tmp_path):
    path = FIXTURES / "protocol" / "det001_bad.py"
    findings = check_file(path, REPO)
    assert findings
    bl_path = tmp_path / "baseline.json"
    write_baseline(findings, bl_path)
    baseline = load_baseline(bl_path)
    # every current finding is grandfathered...
    fresh, old = split_baselined(findings, baseline)
    assert fresh == [] and len(old) == len(findings)
    # ...but a NEW copy of a baselined finding still gates (counts
    # are budgets, not wildcards)
    doubled = findings + [findings[0]]
    fresh2, _old2 = split_baselined(doubled, baseline)
    assert len(fresh2) == 1
    # and the file round-trips through JSON intact
    assert json.loads(bl_path.read_text())["findings"] == {
        k: v for k, v in sorted(baseline.items())
    }


def test_fixture_corpus_walk():
    # the per-rule corpus lives under protocol/ + transport/ (the
    # cross-module registry tree under xmodule/ has its own walk test
    # in tests/test_staticcheck_program.py)
    findings, n_files = check_paths(
        [FIXTURES / "protocol", FIXTURES / "transport"], REPO
    )
    assert n_files == len(BAD_FIXTURES) + len(GOOD_FIXTURES) + 1
    tagged = sum(
        len(expected_findings(FIXTURES / rel)) for rel in BAD_FIXTURES
    )
    # corpus-wide: every tagged line + the two pragma_cases findings
    assert len(findings) == tagged + 2


def test_tree_walks_skip_the_fixture_corpus():
    # scanning tests/ must NOT drown in the corpus's deliberate
    # findings: the walker treats staticcheck_fixtures as test data
    # unless a target points inside it
    findings, n_files = check_paths([REPO / "tests"], REPO)
    assert n_files > 0
    assert not any("staticcheck_fixtures" in f.path for f in findings)


def test_rule_catalog_registered():
    assert set(registered_rules()) == {
        "DET001",
        "DET002",
        "DET003",
        "DET004",
        "DET005",
        "DET006",
        "DET007",
        "CONC001",
        "CONC002",
        "CONC003",
        "CONC004",
        "ERR001",
        "WIRE001",
        "SCHEMA001",
        "ARM001",
        "VERIFY001",
    }


def test_guarded_by_metadata_merges():
    from cleisthenes_tpu.utils.determinism import guarded_by

    @guarded_by("_lock", "_a")
    @guarded_by("_other", "_b", "_c")
    class X:
        pass

    assert X.__guarded_by__ == {
        "_a": "_lock",
        "_b": "_other",
        "_c": "_other",
    }
    with pytest.raises(ValueError):
        guarded_by("_lock")


def test_gate_is_clean_on_the_package():
    """The merged tree ships at zero unbaselined findings with an
    EMPTY baseline (the PR's acceptance criterion), via the same CLI
    entry ci.sh runs."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "cleisthenes_tpu"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(BASELINE_PATH.read_text())["findings"] == {}
