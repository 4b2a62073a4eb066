#!/usr/bin/env bash
# CI gate: the one command that gates the tree.
#
# Mirrors the reference's PR pipeline (reference .travis.yml:24-27 +
# travis/run_on_pull_requests.sh: goimports format gate, `go test -v`,
# then `go test -race`), translated to this stack:
#
#   1. format/syntax gate  — compileall + tools/format_gate.py (the
#      image bakes no third-party formatter; the gate enforces this
#      tree's deterministic style invariants — parseability, LF, EOF
#      newline, no tabs/trailing whitespace, <= 99 cols — stdlib-only)
#   2. staticcheck gate    — tools/staticcheck: the three-pass
#      whole-program analyzer over the package + tools + tests
#      (per-file rules DET001-DET006/CONC001/CONC002/ERR001, the
#      cross-module registry rules WIRE001 wire-kind/pb-tag coverage,
#      SCHEMA001 counter/snapshot/golden-exposition parity, ARM001
#      arm-flag/wave-seam/fingerprint parity, VERIFY001
#      verify-before-dispatch taint walk, plus the pass-3 call-graph
#      rules CONC003 caller-holds-lock discipline, CONC004 blocking
#      reachability from dispatcher callbacks, DET007 interprocedural
#      entropy taint), with --audit-pragmas
#      failing on stale pragmas and pragma-count growth past the
#      budget in baseline.json.  Fails on ANY unbaselined finding;
#      the committed baseline is empty — every sanctioned exception
#      is a justified pragma.  A few seconds and stdlib-only, so
#      CI_FAST runs it too.  Rule catalog: docs/STATICCHECK.md.
#   3. observability gate  — a seeded 4-node traced cluster captures
#      a flight-recorder artifact (utils/trace.py) and
#      tools/tracetool.py --validate gates its schema + per-node
#      monotone sequence numbers, so the tracing plane cannot rot
#      silently between perf rounds (docs/TRACING.md)
#   4. perf-regression gate — tools/perfgate.py runs a seeded traced
#      mini-bench (4 nodes, 3 epochs) and compares epoch p50, the
#      DETERMINISTIC hub-dispatch count, and per-stage wall shares
#      against the trailing BENCH_TREND.jsonl records with noise
#      bands; the first run seeds the trend file (always passes)
#   5. ingress smoke load  — tools/loadgen.py --smoke: a seeded
#      open-loop client band through the production admission path
#      (ingress twin + fee-priority mempool); zero lost acks,
#      settled ⊇ ordered at drain, and byte-identical settled
#      content across pipeline depths gate the merge (ISSUE 18)
#   6. fast test tier      — pytest minus the multi-minute scale
#      tests, under tools/covgate.py (PEP 669 line coverage; the
#      tier must execute >= 85% of the package's executable lines —
#      the travis pipeline's coverage upload, translated to a GATE)
#   7. race-analog tier    — the seeded deterministic-scheduler suites
#      (transport/byzantine), this stack's answer to `-race`
#      (SURVEY.md §5.2: replayable interleavings instead of a dynamic
#      race detector), plus the real-thread gRPC suite
#   8. lock sanitizer      — the lock-sensitive tier-1 subset +
#      a 20-seed fuzz band re-run under CLEISTHENES_LOCKCHECK=1: the
#      runtime @guarded_by sanitizer (utils/lockcheck.py, the dynamic
#      twin of CONC001/CONC003) asserts every guarded attribute
#      access holds its declared lock; zero violations gate
#   9. fault tier          — the crash/partition/adversary suite
#      (`-m faults`: Byzantine coalitions, crash+WAL-restart+CATCHUP,
#      gRPC backoff redial) replayed over a fixed 3-seed matrix, so a
#      fault-handling regression on ANY matrix seed gates the merge
#  10. fuzz smoke          — tools/fuzz.py over a fixed seed band:
#      composite semantic (protocol/byzantine) + wire (Coalition) +
#      crash/partition schedules on seeded 4-node clusters, safety
#      invariants checked at every quiescence point; a violation
#      shrinks to a minimal replayable repro.  The deep band (200
#      seeds) rides the slow tier (tests/test_fuzz.py)
#  11. full tier           — everything, including the N=64 slow test
#      (skipped when CI_FAST=1)
#
# Usage:  ./ci.sh          # full gate
#         CI_FAST=1 ./ci.sh  # pre-push quick gate

set -euo pipefail
cd "$(dirname "$0")"

echo "== [1/11] syntax + format gate"
python -m compileall -q cleisthenes_tpu tests bench.py chip_smoke.py __graft_entry__.py
python tools/format_gate.py

echo "== [2/11] staticcheck gate: whole-program registry + determinism plane"
python -m tools.staticcheck cleisthenes_tpu tools tests --audit-pragmas

echo "== [3/11] observability gate: traced seeded cluster -> tracetool --validate"
TRACE_ARTIFACT="$(mktemp /tmp/cleisthenes_trace_ci.XXXXXX.json)"
trap 'rm -f "$TRACE_ARTIFACT"' EXIT
JAX_PLATFORMS=cpu python -m tools.tracetool \
    --capture "$TRACE_ARTIFACT" --n 4 --seed 7 --txs 24
python -m tools.tracetool "$TRACE_ARTIFACT" --validate

echo "== [4/11] perf-regression gate: seeded mini-bench vs BENCH_TREND.jsonl"
# seeded traced mini-bench through tools/perfgate.py; seeds the trend
# on the first run, gates epoch-p50 / dispatch-count / stage-share
# regressions (noise-banded) on every later run and appends on pass
JAX_PLATFORMS=cpu python -m tools.perfgate --trend BENCH_TREND.jsonl

echo "== [5/11] ingress smoke load: seeded open-loop client band"
# tools/loadgen.py --smoke (ISSUE 18): a seconds-scale seeded Pareto
# client population driven through the production admission path (the
# in-proc twin of the client gRPC surface + fee-priority mempool).
# The harness asserts zero lost acks (every OK-acked tx settles
# exactly once or is accounted by the eviction counter), the settled
# frontier catching the ordered frontier at drain, cross-node
# agreement, and byte-identical settled content across pipeline
# depths 1 and 4 before reporting any latency
JAX_PLATFORMS=cpu python -m tools.loadgen --smoke

echo "== [6/11] fast tests (with coverage gate)"
COVGATE_MIN="${COVGATE_MIN:-85}" \
    python -m pytest tests/ -q -m "not slow" -x -p tools.covgate

echo "== [7/11] race-analog: seeded-scheduler + threaded-transport suites"
python -m pytest tests/test_transport.py tests/test_byzantine.py \
    tests/test_semantic_byzantine.py tests/test_grpc.py -q -x -m "not slow"

echo "== [8/11] lock sanitizer: @guarded_by runtime assertions armed"
# the same annotation registry staticcheck proves statically, watched
# dynamically: every guarded attribute access must hold its declared
# lock (utils/lockcheck.py); the lock-sensitive suites + one fuzz
# band run armed, so a discipline hole the static rules cannot see
# (dynamic dispatch, callbacks) still gates
CLEISTHENES_LOCKCHECK=1 python -m pytest tests/test_transport.py \
    tests/test_hub.py tests/test_ledger.py tests/test_lockcheck.py \
    -q -x -m "not slow"
LOCKCHECK_FUZZ_OUT="$(mktemp -d /tmp/cleisthenes_fuzz_lc.XXXXXX)"
CLEISTHENES_LOCKCHECK=1 JAX_PLATFORMS=cpu python -m tools.fuzz \
    --seeds 0:20 --out "$LOCKCHECK_FUZZ_OUT"
rm -rf "$LOCKCHECK_FUZZ_OUT"

echo "== [9/11] fault gate: crash/partition/adversary suite, 3-seed matrix"
# the full faults-marked suite already ran at the default seed in
# stages 4-5; the matrix replays the FAULT_SEED-parametrized
# crash+WAL-restart+CATCHUP scenario (the seed-sensitive entry point)
# at every matrix seed, so a fault regression on ANY seed gates
for seed in 11 23 47; do
    echo "   -- FAULT_SEED=$seed"
    FAULT_SEED="$seed" python -m pytest tests/test_byzantine.py -q -x \
        -m faults -k crash_restart_wal_catchup
done

echo "== [10/11] fuzz smoke: semantic+wire schedule fuzzer, 20-seed band"
# 4-node seeded clusters, composite behavior/wire/crash schedules;
# any invariant violation exits non-zero, leaving the shrunken repro
# + trace artifact in FUZZ_OUT (cleaned only on success)
FUZZ_OUT="$(mktemp -d /tmp/cleisthenes_fuzz_ci.XXXXXX)"
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:20 --out "$FUZZ_OUT"
# dynamic-membership band: the same composite schedules run ACROSS a
# join/retire reshare ceremony and its activation boundary — ledger,
# roster-version and key-material agreement must span the roster
# change (the 200-seed deep sweep rides the slow tier,
# tests/test_fuzz.py::test_fuzz_reconfig_deep_sweep)
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:20 --reconfig \
    --rounds 16 --out "$FUZZ_OUT"
# K-deep pipelined-frontier band (ISSUE 15): the same composite
# schedules PINNED to depth 2 and depth 4 — the cross-frontier
# invariants (settled prefix ⊆ ordered log, byte-identical honest
# ordered logs, decrypt-lag bound) must hold over the widened
# in-flight window (the 200-seed deep sweep rides the slow tier,
# tests/test_fuzz.py::test_fuzz_pipeline_deep_sweep)
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:10 \
    --pipeline-depth 2 --out "$FUZZ_OUT"
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 10:20 \
    --pipeline-depth 4 --out "$FUZZ_OUT"
# WAN emulation band (ISSUE 16): the same composite schedules over a
# seeded link-model plane — per-link latency/jitter/loss/bandwidth,
# heavy-tailed stragglers — with the profile itself drawn from the
# seed; every invariant must hold under geo-realistic delivery
# schedules (the 200-seed deep sweep rides the slow tier,
# tests/test_fuzz.py::test_fuzz_wan_deep_sweep)
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:20 --wan \
    --out "$FUZZ_OUT"
# client-ingress band (ISSUE 18): every tx submits through the
# in-proc twin of the client gRPC surface — encoded client frames ->
# IngressPlane -> fee-priority mempool — with capacity/client-cap/
# dup schedules drawn from the seed (appended LAST, extending the
# historical stream); gates the settle-exactly-once invariant: every
# acked-and-unevicted tx settles exactly once, dedup/backpressure
# acks honor the admission contract, and subscribe(0) replays the
# settled epochs gap-free
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:20 --ingress \
    --out "$FUZZ_OUT"
# attested reduced-quorum band (ISSUE 19): n = 2f+1 rosters under the
# simulated-TEE trust model — attested_log + reduced_quorum armed,
# equivocator-biased adversaries — gating the attestation invariants
# on top of the classic ones: no honest node is ever accused, every
# equivocation the vault refused shows up in the directory's accused
# set, and the honest ledgers stay byte-identical at n - f quorums
# (appended LAST, extending the historical stream)
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:20 \
    --reduced-quorum --out "$FUZZ_OUT"
# lane shard-out band (ISSUE 20): Config.lanes drawn from {2,3,4}
# per seed (appended LAST, extending the historical stream) — S
# parallel HBBFT lanes over one roster with hash-partitioned
# admission and the deterministic cross-lane total-order merge —
# gating merge-determinism (honest merged orders byte-identical),
# cross-lane settle-exactly-once and the per-lane two-frontier
# invariants (the 200-seed deep sweep rides the slow tier,
# tests/test_fuzz.py::test_fuzz_lanes_deep_sweep)
JAX_PLATFORMS=cpu python -m tools.fuzz --seeds 0:20 --lanes \
    --out "$FUZZ_OUT"
rm -rf "$FUZZ_OUT"

if [[ "${CI_FAST:-0}" == "1" ]]; then
    echo "== [11/11] skipped (CI_FAST=1)"
else
    echo "== [11/11] full suite incl. scale tests"
    python -m pytest tests/ -q -m slow
fi

echo "== CI gate PASSED"
