"""perfgate: the perf-regression observatory's CI gate.

BENCH_*.json files are ad-hoc snapshots: one number per round, no
trend, nothing watching the trajectory between rounds.  This tool
closes that gap with a durable append-only trend file
(``BENCH_TREND.jsonl``, one JSON record per measured run keyed by a
config fingerprint) and a gate that compares a fresh seeded mini-bench
against the trailing trend with noise bands:

- **epoch p50** regresses when the fresh median exceeds
  ``max(trend_median * (1 + rel_tol), trend_median + abs_tol_ms)`` —
  the relative band absorbs CI-host noise, the absolute floor keeps
  tiny mini-bench epochs from turning microseconds of jitter into
  failures.
- **hub dispatches** (the cost model of this stack, and DETERMINISTIC
  for a seeded run) regress when the fresh count exceeds the trend
  maximum by more than ``dispatch_tol`` — a wave-batching regression
  fails here with zero noise before it ever shows up in wall time.
- **stage shares** (where the epoch's wall time goes, from the PR-3
  critical-path attribution) regress when any stage's share grows by
  more than ``share_tol`` absolute — a latency leak that hides inside
  an unchanged total still moves its stage's share.  Shares are a
  wall-clock attribution, so two noise absorbers apply: a fresh run
  whose own epoch p50 is inflated past the trend is not share-gated
  at all (its stall is host noise, attributed to whichever stage the
  scheduler parked on), and a share-only failure is re-measured with
  each stage's minimum share across samples — a real leak reproduces
  on every sample, a stall does not.

Workflow (the ci.sh stage):

    python -m tools.perfgate --trend BENCH_TREND.jsonl

First run seeds the trend (pass); later runs gate against the trailing
``--window`` records with a matching fingerprint and append on pass,
so the band tracks legitimate drift.  After an INTENTIONAL perf change
(more dispatches by design, a new stage), refresh with ``--reset``.
``--record FILE`` gates a pre-measured record instead of running the
mini-bench — the test hook proving the gate actually fails on an
inflated epoch p50.

``bench.py`` appends every full benchmark run's sections through
``append_bench_trend`` so the headline numbers build the same history.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_TREND = REPO_ROOT / "BENCH_TREND.jsonl"

# mini-bench shape: small enough for a CI stage (~seconds), big enough
# that epoch p50 moves when the protocol path regresses
MINI_N = 4
MINI_BATCH = 64
MINI_EPOCHS = 3
MINI_SEED = 1999

DEFAULT_WINDOW = 20
# ingress mini-load shape (ISSUE 18): small enough for a CI stage,
# big enough that submit->ordered p50 moves when the admission path
# or the drain seam regresses
INGRESS_CLIENTS = 400
INGRESS_TXS = 400
INGRESS_TICKS = 6
INGRESS_BATCH = 64
DEFAULT_REL_TOL = 1.0  # fresh p50 may double before failing (CI noise)
DEFAULT_ABS_TOL_MS = 50.0
DEFAULT_SHARE_TOL = 0.25
DEFAULT_DISPATCH_TOL = 1.25


# ---------------------------------------------------------------------------
# trend file
# ---------------------------------------------------------------------------


def fingerprint_key(record: Dict) -> str:
    """Stable comparison key: records gate only against runs of the
    identical configuration."""
    return json.dumps(record.get("fingerprint", {}), sort_keys=True)


def load_trend(path: str) -> List[Dict]:
    """Every parseable record, file order (oldest first).  A corrupt
    line (torn write) is skipped, never fatal — the trend is an aid,
    not a ledger."""
    out: List[Dict] = []
    try:
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        return []
    return out


def append_record(path: str, record: Dict) -> None:
    with open(path, "a", encoding="utf-8") as fh:
        fh.write(json.dumps(record, sort_keys=True) + "\n")


def append_bench_trend(result: Dict, path: str = str(DEFAULT_TREND)) -> int:
    """Fold one bench.py artifact into the trend: a record per
    protocol section per backend that produced an epoch p50.  Returns
    the number of records appended; never raises (bench output must
    not become hostage to trend bookkeeping)."""
    appended = 0
    try:
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        platform = result.get("platform")
        for section, body in result.items():
            if not isinstance(body, dict):
                continue
            for backend in ("tpu", "cpu"):
                side = body.get(backend)
                if not isinstance(side, dict):
                    continue
                p50 = side.get("epoch_p50_ms")
                if p50 is None:
                    continue
                record = {
                    "kind": "bench_section",
                    "ts": stamp,
                    "fingerprint": {
                        "kind": "bench_section",
                        "section": section,
                        "backend": backend,
                        "platform": platform,
                        "n": body.get("n"),
                        "batch": body.get("batch"),
                    },
                    "epoch_p50_ms": p50,
                    # two-frontier split (ISSUE 8): ordered-frontier
                    # p50, settled p50 and the trailing-lag p95 ride
                    # every protocol section that measures them
                    "ordered_epoch_p50_ms": side.get(
                        "ordered_epoch_p50_ms"
                    ),
                    "settled_epoch_p50_ms": side.get(
                        "settled_epoch_p50_ms"
                    ),
                    "decrypt_lag_p95_ms": side.get("decrypt_lag_p95_ms"),
                    "epoch_times_ms": side.get("epoch_times_ms"),
                    "tx_per_sec": side.get("tx_per_sec"),
                    "stage_shares": side.get("stage_shares"),
                    "hub_dispatches": side.get("hub_dispatches_cluster"),
                    # columnar-wave counters (ISSUE 7): present on
                    # protocol sections since the wave-batched hub
                    "dispatches_per_epoch": side.get(
                        "dispatches_per_epoch"
                    ),
                    "wave_width_p50": side.get("wave_width_p50"),
                    "wave_width_p95": side.get("wave_width_p95"),
                    # delivery-plane counters (ISSUE 9)
                    "frames_decoded_per_epoch": side.get(
                        "frames_decoded_per_epoch"
                    ),
                    "mac_verifies_per_epoch": side.get(
                        "mac_verifies_per_epoch"
                    ),
                    "decode_memo_hit_rate": side.get(
                        "decode_memo_hit_rate"
                    ),
                    # wave-routed ingest (ISSUE 10)
                    "handler_dispatches_per_epoch": side.get(
                        "handler_dispatches_per_epoch"
                    ),
                    # egress columnarization (ISSUE 13)
                    "frames_encoded_per_epoch": side.get(
                        "frames_encoded_per_epoch"
                    ),
                    "mac_signs_per_epoch": side.get(
                        "mac_signs_per_epoch"
                    ),
                    "encode_memo_hit_rate": side.get(
                        "encode_memo_hit_rate"
                    ),
                    "coin_dispatches_per_epoch": side.get(
                        "coin_dispatches_per_epoch"
                    ),
                }
                append_record(path, record)
                appended += 1
        # lane shard-out cadence (ISSUE 20): one record per lane-count
        # arm of the bench lane_scaling section — the virtual-time
        # throughput and dispatch-flatness trend across S
        lanes = result.get("lane_scaling")
        if isinstance(lanes, dict):
            for arm, body in lanes.get("arms", {}).items():
                if not isinstance(body, dict):
                    continue
                append_record(path, {
                    "kind": "bench_lane_scaling",
                    "ts": stamp,
                    "fingerprint": {
                        "kind": "bench_lane_scaling",
                        "arm": arm,
                        "lanes": body.get("lanes"),
                        "n": body.get("n"),
                        "batch": body.get("batch"),
                        "platform": platform,
                    },
                    "tx_per_virtual_sec": body.get("tx_per_virtual_sec"),
                    "wall_tx_per_sec": body.get("wall_tx_per_sec"),
                    "virtual_ms_per_slot": body.get("virtual_ms_per_slot"),
                    "merged_slots": body.get("merged_slots"),
                    "hub_dispatches_per_ordered_epoch": body.get(
                        "hub_dispatches_per_ordered_epoch"
                    ),
                })
                appended += 1
    except OSError:
        pass
    return appended


# ---------------------------------------------------------------------------
# the seeded mini-bench
# ---------------------------------------------------------------------------


def run_sample(
    n: int = MINI_N,
    batch: int = MINI_BATCH,
    epochs: int = MINI_EPOCHS,
    seed: int = MINI_SEED,
) -> Dict:
    """One seeded traced mini-bench over the in-proc cluster: epoch
    walls, stage shares, wave sizes, hub dispatch count."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster
    from cleisthenes_tpu.utils.trace import to_chrome
    from tools import tracetool

    cfg = Config(
        n=n, batch_size=batch, seed=seed, trace=True,
        crypto_backend="cpu",
    )
    cluster = SimulatedCluster(
        config=cfg,
        seed=seed,
        key_seed=7,
        auto_propose=False,
    )
    ids = cluster.ids
    total = batch * (epochs + 1)  # +1: the warm-up epoch's own txs
    for i in range(total):
        cluster.submit(b"perfgate-%08d" % i, node_id=ids[i % n])
    for hb in cluster.nodes.values():  # warm-up epoch (compile, caches)
        hb.start_epoch()
    cluster.net.run()
    walls: List[float] = []
    for _ in range(epochs):
        t0 = time.perf_counter()
        for hb in cluster.nodes.values():
            hb.start_epoch()
        cluster.net.run()
        walls.append(time.perf_counter() - t0)
    cluster.assert_agreement()
    doc = to_chrome(cluster.trace_events())
    summary = tracetool.summarize(doc)
    p50 = statistics.median(walls)
    p95 = sorted(walls)[max(0, int(round(0.95 * (len(walls) - 1))))]
    # two-frontier commit split (ISSUE 8): the per-epoch latencies as
    # the node metrics saw them — propose -> ciphertext-ordered commit
    # (the protocol-plane number the gate now keys on), propose ->
    # settled plaintext, and the trailing decrypt lag's p95
    m = cluster.nodes[ids[0]].metrics
    ordered_p50 = m.ordered_latency.p50
    settled_p50 = m.epoch_latency.p50
    lag_p95 = m.settle_lag_latency.p95
    dstats = cluster.net.delivery_stats()
    probes = dstats["decode_memo_hits"] + dstats["decode_memo_misses"]
    return {
        "kind": "perfgate_mini",
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "fingerprint": {
            "kind": "perfgate_mini",
            "n": n,
            "batch": batch,
            "epochs": epochs,
            "seed": seed,
            "backend": "cpu",
            # the commit mode changes what the epoch windows (and so
            # the stage shares) MEAN — runs must never gate against
            # trend records measured under the other mode
            "order_then_settle": bool(cfg.order_then_settle),
            # every declared arm flag (config.ARM_FLAGS) keys the
            # fingerprint (staticcheck ARM001 cross-checks the set):
            # epoch pipelining changes what the epoch windows overlap
            "epoch_pipelining": bool(cfg.epoch_pipelining),
            # K-deep pipelined frontiers (ISSUE 15): the depth
            # changes how many epochs share each wave — and with
            # them what every per-epoch dispatch counter MEANS — so
            # runs gate only against same-depth trend records
            "pipeline_depth": int(cfg.pipeline_depth),
            # the trust-model arms (ISSUE 19): the attested sender
            # log adds a per-frame stamp+verify to every MAC, and the
            # reduced-quorum mode changes the quorum arithmetic the
            # epochs wait on (f=(n-1)//2 instead of f=(n-1)//3) —
            # both change what the epoch windows and sign/verify
            # counters MEAN, so runs gate only against same-mode
            # trend records
            "attested_log": bool(cfg.attested_log),
            "reduced_quorum": bool(cfg.reduced_quorum),
            # lane shard-out (ISSUE 20): S lanes share each wave's
            # dispatches, so every per-epoch counter and latency
            # window MEANS something different at a different S —
            # runs gate only against same-lane-count trend records
            # (the int-valued arm key; staticcheck ARM001 checks it)
            "lanes": int(cfg.lanes),
            # the ingress mini-load's shape changes what the
            # submit->ordered p50 and the eviction count MEAN —
            # reshaping it re-keys the trend (run --reset after an
            # intentional change)
            "ingress": {
                "clients": INGRESS_CLIENTS,
                "txs": INGRESS_TXS,
                "ticks": INGRESS_TICKS,
                "batch": INGRESS_BATCH,
            },
        },
        "epoch_p50_ms": round(p50 * 1000.0, 3),
        "epoch_p95_ms": round(p95 * 1000.0, 3),
        "ordered_epoch_p50_ms": (
            round(ordered_p50 * 1000.0, 3)
            if ordered_p50 is not None
            else None
        ),
        "settled_epoch_p50_ms": (
            round(settled_p50 * 1000.0, 3)
            if settled_p50 is not None
            else None
        ),
        "decrypt_lag_p95_ms": (
            round(lag_p95 * 1000.0, 3) if lag_p95 is not None else None
        ),
        "epoch_times_ms": [round(w * 1000.0, 1) for w in walls],
        "stage_shares": tracetool.stage_shares(doc),
        "wave_size_p50": summary["wave_size_p50"],
        "wave_size_p95": summary["wave_size_p95"],
        "hub_dispatches": int(
            cluster.nodes[ids[0]].hub.stats()["dispatches"]
        ),
        # delivery-plane counters (ISSUE 9) — deterministic for the
        # seeded schedule, gated like hub_dispatches: a delivery-
        # columnarization regression (memo stops hitting, waves stop
        # batching) fails here with zero noise
        "frames_decoded": int(dstats["frames_decoded"]),
        "mac_verifies": int(dstats["mac_verifies"]),
        "decode_memo_hit_rate": (
            round(dstats["decode_memo_hits"] / probes, 4)
            if probes
            else 0.0
        ),
        # wave-routed ingest (ISSUE 10): batch handler invocations
        # crossing the router seam, cluster-wide — deterministic for
        # the seeded schedule, gated like hub_dispatches (a routing
        # regression — columns stop forming, the router falls back to
        # per-payload dispatch — fails here with zero noise)
        "handler_dispatches": int(
            sum(
                hb.metrics.handler_dispatches.value
                for hb in cluster.nodes.values()
            )
        ),
        # egress columnarization (ISSUE 13): outbound encode+sign
        # passes and native coin-issue dispatches — deterministic for
        # the seeded schedule, gated like the delivery counters (an
        # egress regression — the memo stops sharing, waves stop
        # folding, the coin pool stops batching — fails with zero
        # noise)
        "frames_encoded": int(dstats["frames_encoded"]),
        "mac_signs": int(dstats["mac_signs"]),
        "encode_memo_hit_rate": (
            round(
                dstats["encode_memo_hits"]
                / (dstats["encode_memo_hits"] + dstats["encode_memo_misses"]),
                4,
            )
            if (dstats["encode_memo_hits"] + dstats["encode_memo_misses"])
            else 0.0
        ),
        "coin_dispatches": int(
            cluster.nodes[ids[0]].hub.stats()["coin_issue_batches"]
        ),
        # ingress plane (ISSUE 18): a seeded mini load through the
        # production admission path (tools/loadgen.py arm — in-proc
        # twin of the client gRPC surface + fee-priority mempool).
        # submit_to_ordered_p50_ms is the client-visible protocol-
        # plane latency (wall clock: gated with the same noise band
        # as the epoch p50); mempool_evictions is DETERMINISTIC for
        # the seeded schedule and must stay zero — the mini load is
        # sized to fit the pool, so any eviction is an admission-
        # policy regression, not pressure
        **_ingress_sample(seed),
    }


def _ingress_sample(seed: int) -> Dict:
    """The ingress mini-load: one seconds-scale loadgen arm over the
    shared production path (shape below is part of the fingerprint —
    changing it re-keys the trend, see --reset)."""
    from tools import loadgen

    sched = loadgen.build_schedule(
        clients=INGRESS_CLIENTS, txs=INGRESS_TXS, ticks=INGRESS_TICKS,
        seed=seed,
    )
    arm = loadgen.run_arm(
        sched, depth=2, n=MINI_N, batch=INGRESS_BATCH, seed=seed
    )
    return {
        "submit_to_ordered_p50_ms": arm["submit_to_ordered_ms"]["p50"],
        "submit_to_settled_p50_ms": arm["submit_to_settled_ms"]["p50"],
        "mempool_evictions": int(arm["evicted"]),
    }


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def compare(
    fresh: Dict,
    trend: List[Dict],
    rel_tol: float = DEFAULT_REL_TOL,
    abs_tol_ms: float = DEFAULT_ABS_TOL_MS,
    share_tol: float = DEFAULT_SHARE_TOL,
    dispatch_tol: float = DEFAULT_DISPATCH_TOL,
) -> Tuple[bool, List[str]]:
    """(ok, reasons): gate ``fresh`` against same-fingerprint ``trend``
    records (the caller already windowed and filtered them)."""
    reasons: List[str] = []
    # the gate keys on the ORDERED-frontier epoch p50 when the fresh
    # record and the trend both carry it (two-frontier commit split:
    # the protocol-plane latency an application's ordering sees);
    # records from before the split — or coupled-arm runs — fall back
    # to the classic settled/loop epoch p50
    key = "epoch_p50_ms"
    if isinstance(
        fresh.get("ordered_epoch_p50_ms"), (int, float)
    ) and any(
        isinstance(r.get("ordered_epoch_p50_ms"), (int, float))
        for r in trend
    ):
        key = "ordered_epoch_p50_ms"
    p50s = [
        r[key] for r in trend if isinstance(r.get(key), (int, float))
    ]
    if p50s:
        med = statistics.median(p50s)
        limit = max(med * (1.0 + rel_tol), med + abs_tol_ms)
        fresh_p50 = fresh.get(key)
        if not isinstance(fresh_p50, (int, float)):
            reasons.append(f"fresh record carries no {key}")
        elif fresh_p50 > limit:
            reasons.append(
                f"{key} regression: {fresh_p50:.3f} ms > "
                f"noise-band limit {limit:.3f} ms "
                f"(trend median {med:.3f} ms over {len(p50s)} runs)"
            )
    # client-visible ingress latency (ISSUE 18): submit->ordered p50
    # through the production admission path, same noise band as the
    # epoch p50 above (wall-clock: the relative band absorbs CI-host
    # noise, the absolute floor keeps mini-load jitter honest)
    ing_p50s = [
        r["submit_to_ordered_p50_ms"]
        for r in trend
        if isinstance(r.get("submit_to_ordered_p50_ms"), (int, float))
    ]
    fresh_ing = fresh.get("submit_to_ordered_p50_ms")
    if ing_p50s and isinstance(fresh_ing, (int, float)):
        med = statistics.median(ing_p50s)
        limit = max(med * (1.0 + rel_tol), med + abs_tol_ms)
        if fresh_ing > limit:
            reasons.append(
                f"submit_to_ordered_p50_ms regression: "
                f"{fresh_ing:.3f} ms > noise-band limit {limit:.3f} ms "
                f"(trend median {med:.3f} ms over {len(ing_p50s)} runs)"
            )
    # deterministic-counter gates: hub dispatches (PR 7) and the
    # delivery-plane frame/MAC counters (ISSUE 9) share one rule —
    # the seeded schedule makes them exact, so exceeding the trend
    # maximum by more than dispatch_tol is a structural regression
    for counter, what in (
        ("hub_dispatches", "hub dispatch"),
        ("frames_decoded", "frame-decode"),
        ("mac_verifies", "MAC-verify"),
        ("handler_dispatches", "handler-dispatch"),
        ("frames_encoded", "frame-encode"),
        ("mac_signs", "MAC-sign"),
        ("coin_dispatches", "coin-dispatch"),
        # the seeded ingress mini-load fits its pool by construction,
        # so the eviction count is deterministic (zero on a healthy
        # run): any fresh eviction is an admission-policy regression
        ("mempool_evictions", "mempool-eviction"),
    ):
        history = [
            r[counter] for r in trend if isinstance(r.get(counter), int)
        ]
        fresh_v = fresh.get(counter)
        if history and isinstance(fresh_v, int):
            cap = max(history) * dispatch_tol
            if fresh_v > cap:
                reasons.append(
                    f"{what} regression: {fresh_v} > "
                    f"{cap:.0f} (trend max {max(history)} * "
                    f"{dispatch_tol}); the seeded run is deterministic "
                    "— this is a batching change, not noise "
                    "(--reset if intentional)"
                )
    trend_shares = [
        r["stage_shares"]
        for r in trend
        if isinstance(r.get("stage_shares"), dict) and r["stage_shares"]
    ]
    fresh_shares = fresh.get("stage_shares")
    # stage shares are only comparable between runs of similar wall:
    # on a loaded host the scheduler's stall lands on whichever stage
    # it happened to park in, inflating that stage's share while
    # saying nothing about the code.  Host noise inflates the GATE
    # KEY's p50 too (the stall sits inside the ordered window), so
    # skip the share gate only when the same p50 the band above
    # gated on is itself inflated past the trend — a settle-track
    # leak that keeps the ordered p50 flat stays share-gated.
    fresh_key_p50 = fresh.get(key)
    if (
        p50s
        and isinstance(fresh_key_p50, (int, float))
        and fresh_key_p50 > statistics.median(p50s) * 1.25
    ):
        fresh_shares = None
    if trend_shares and isinstance(fresh_shares, dict):
        stages = {s for shares in trend_shares for s in shares}
        for stage in sorted(stages | set(fresh_shares)):
            med_share = statistics.median(
                [float(s.get(stage, 0.0)) for s in trend_shares]
            )
            got = float(fresh_shares.get(stage, 0.0))
            if got - med_share > share_tol:
                reasons.append(
                    f"stage-share regression: {stage} owns "
                    f"{got:.2%} of epoch wall vs trend median "
                    f"{med_share:.2%} (+>{share_tol:.0%})"
                )
    return (not reasons), reasons


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="tools.perfgate")
    ap.add_argument(
        "--trend", default=str(DEFAULT_TREND),
        help=f"trend JSONL path (default {DEFAULT_TREND.name})",
    )
    ap.add_argument(
        "--record", metavar="JSON",
        help="gate this pre-measured record file instead of running "
        "the mini-bench (never appended)",
    )
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW)
    ap.add_argument("--rel-tol", type=float, default=DEFAULT_REL_TOL)
    ap.add_argument("--abs-tol-ms", type=float, default=DEFAULT_ABS_TOL_MS)
    ap.add_argument("--share-tol", type=float, default=DEFAULT_SHARE_TOL)
    ap.add_argument(
        "--dispatch-tol", type=float, default=DEFAULT_DISPATCH_TOL
    )
    ap.add_argument(
        "--no-append", action="store_true",
        help="gate only; do not extend the trend on pass",
    )
    ap.add_argument(
        "--reset", action="store_true",
        help="drop same-fingerprint history first (after an "
        "INTENTIONAL perf change) and reseed from this run",
    )
    ap.add_argument("--n", type=int, default=MINI_N)
    ap.add_argument("--batch", type=int, default=MINI_BATCH)
    ap.add_argument("--epochs", type=int, default=MINI_EPOCHS)
    ap.add_argument("--seed", type=int, default=MINI_SEED)
    args = ap.parse_args(argv)

    if args.record:
        with open(args.record, "r", encoding="utf-8") as fh:
            fresh = json.load(fh)
    else:
        fresh = run_sample(
            n=args.n, batch=args.batch, epochs=args.epochs, seed=args.seed
        )
    key = fingerprint_key(fresh)
    trend_all = load_trend(args.trend)
    if args.reset:
        kept = [r for r in trend_all if fingerprint_key(r) != key]
        tmp = args.trend + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            for r in kept:
                fh.write(json.dumps(r, sort_keys=True) + "\n")
        os.replace(tmp, args.trend)
        trend_all = kept
    matching = [r for r in trend_all if fingerprint_key(r) == key]
    matching = matching[-args.window:]

    if not matching:
        if args.record:
            print(
                "perfgate: no trend history for this fingerprint and "
                "--record given; nothing to gate against"
            )
            return 0
        append_record(args.trend, fresh)
        print(
            f"perfgate: seeded trend {args.trend} "
            f"(epoch p50 {fresh['epoch_p50_ms']} ms, "
            f"{fresh.get('hub_dispatches')} hub dispatches) — PASS"
        )
        return 0

    ok, reasons = compare(
        fresh,
        matching,
        rel_tol=args.rel_tol,
        abs_tol_ms=args.abs_tol_ms,
        share_tol=args.share_tol,
        dispatch_tol=args.dispatch_tol,
    )
    if not ok and not args.record and all(
        "stage-share" in r for r in reasons
    ):
        # a scheduler stall lands on whichever stage the host parked
        # the process in, inflating that stage's share for ONE sample;
        # a real latency leak reproduces on every sample.  Re-measure
        # and keep each stage's minimum share across samples before
        # declaring a regression.
        shares_min = {
            s: float(v)
            for s, v in (fresh.get("stage_shares") or {}).items()
        }
        for _ in range(2):
            resample = run_sample(
                n=args.n,
                batch=args.batch,
                epochs=args.epochs,
                seed=args.seed,
            )
            re_shares = resample.get("stage_shares") or {}
            shares_min = {
                s: min(v, float(re_shares.get(s, 0.0)))
                for s, v in shares_min.items()
            }
            ok, reasons = compare(
                dict(fresh, stage_shares=shares_min),
                matching,
                rel_tol=args.rel_tol,
                abs_tol_ms=args.abs_tol_ms,
                share_tol=args.share_tol,
                dispatch_tol=args.dispatch_tol,
            )
            if ok:
                break
    med = statistics.median(
        [
            r["epoch_p50_ms"]
            for r in matching
            if isinstance(r.get("epoch_p50_ms"), (int, float))
        ]
        or [0.0]
    )
    if ok:
        if not args.record and not args.no_append:
            append_record(args.trend, fresh)
        print(
            f"perfgate: PASS — epoch p50 "
            f"{fresh.get('epoch_p50_ms')} ms within band of trend "
            f"median {med:.3f} ms ({len(matching)} run(s))"
        )
        return 0
    print("perfgate: FAIL")
    for r in reasons:
        print(f"  - {r}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
