"""abench: same-box interleaved A/B protocol bench — HEAD vs a git ref.

WAVE_EVIDENCE.md (and the r05 round notes) document the failure mode
this tool exists for: the recorded 12.2 s protocol_n64 baseline does
NOT reproduce on another box (HEAD itself measured 18.6-34 s there),
so comparing a fresh BENCH_*.json against a band recorded elsewhere
is unusable.  What DOES hold up is a paired comparison: run the two
code versions alternately on the SAME box inside ONE harness lifetime
(A B A B ...), so drift, thermal state and background load hit both
arms symmetrically, and report per-pair deltas instead of absolute
numbers.

    python -m tools.abench BASE_REF [--n 16] [--batch 256]
           [--epochs 3] [--pairs 4] [--seed 99]
    python bench.py --ab BASE_REF        # same thing

Mechanics: ``git worktree add --detach`` materializes BASE_REF under
``.abench/`` inside the repo, each sample runs in a fresh subprocess
with its cwd at the matching tree (two code versions cannot share one
interpreter), and the probe script uses only APIs stable since PR 1
(Config, SimulatedCluster, the manual propose-and-drain loop) so any
recent ref can serve as the base arm.  Every subprocess pins
JAX_PLATFORMS=cpu, so no sample ever needs the chip its parent (or
anyone else) may hold: a CPU A/B compares code paths and counts.

Output: one JSON line — per-arm samples, per-pair head/base ratios,
and their medians.  ``epoch_p50_ratio_median < 1`` means HEAD is
faster.  ``ordered_epoch_p50_ms`` rides along when the arm's code
exposes it (the ISSUE-8 two-frontier split; older refs report null).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
WORKTREE_DIR = REPO_ROOT / ".abench"

# The probe every arm runs: manual propose-and-drain epochs over the
# in-proc cluster, ONE JSON line on stdout.  Only touches APIs that
# exist on every ref this harness will realistically compare, and
# degrades gracefully (nulls) where a ref lacks the newer metrics.
_PROBE = r"""
import json, os, statistics, sys, time
import numpy as np
from cleisthenes_tpu.config import Config
from cleisthenes_tpu.protocol.cluster import SimulatedCluster

n, batch, epochs, seed = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
)
# per-arm Config overrides (ABENCH_CONFIG_OVERRIDES, a JSON object of
# Config kwargs): the ISSUE-15 depth A/B pits pipeline_depth=K
# against depth 1 on the SAME code — only pass overrides to arms
# whose tree knows the fields
overrides = json.loads(os.environ.get("ABENCH_CONFIG_OVERRIDES", "{}"))
# an arm may override the roster size itself (the ISSUE-19 trust-model
# A/B pits a reduced-quorum n=2f+1 roster against the baseline 3f+1
# roster at EQUAL f): an "n" in the overrides replaces the argv n for
# that arm instead of colliding with it in the Config call
n = int(overrides.pop("n", n))
# pseudo-override "wan_profile" mounts the ISSUE-16 link model on the
# cluster (it is a SimulatedCluster kwarg, not a Config field): the
# ISSUE-20 lane A/B pairs tx-per-VIRTUAL-second across S, since wall
# throughput in the serialized one-process scheduler pays every
# lane's crypto sequentially and cannot show the shard-out win
wan = overrides.pop("wan_profile", None)
# a lanes override shards the arm into S sibling lanes (ISSUE 20);
# the submitted tx mass scales by S so every lane runs SATURATED
# epochs — the throughput-benchmark shape — and the per-settled-tx
# cost fields stay directly comparable across unequal masses
S = int(overrides.get("lanes", 1))
# the production shape: work pre-submitted, auto-propose on, ONE
# net.run chains every epoch back to back — the shape where cross-
# epoch pipelining (old or two-frontier) is actually reachable.
cluster = SimulatedCluster(
    config=Config(
        n=n, batch_size=batch, crypto_backend="cpu", seed=seed,
        **overrides
    ),
    key_seed=77,
    auto_propose=True,
    **({"wan_profile": wan} if wan else {}),
)
ids = cluster.ids
rng = np.random.default_rng(13)
for i in range(batch * S):  # warm-up epoch (compile, caches), its own txs
    cluster.nodes[ids[i % n]].add_transaction(
        rng.integers(0, 256, size=64, dtype=np.uint8).tobytes()
    )
for hb in cluster.nodes.values():  # explicit kick: add_transaction
    hb.start_epoch()               # never opens an epoch by itself
cluster.net.run()
assert len(cluster.nodes[ids[0]].committed_batches) >= 1
for i in range(batch * epochs * S):
    cluster.nodes[ids[i % n]].add_transaction(
        rng.integers(0, 256, size=64, dtype=np.uint8).tobytes()
    )
n0 = cluster.nodes[ids[0]]


def merged_log(node):
    # the ISSUE-20 merged total order when the tree has lanes; the
    # plain settled log (identical at lanes=1) on older refs
    log = getattr(node, "merged_batches", None)
    return log if log is not None else node.committed_batches


def virtual_ms():
    w = getattr(cluster.net, "wan", None)
    return int(w.stats()["virtual_time_ms"]) if w is not None else None


before = len(merged_log(n0))
v_before = virtual_ms()
t0 = time.perf_counter()
for hb in cluster.nodes.values():  # kick; auto-propose chains on
    hb.start_epoch()
cluster.net.run()
elapsed = time.perf_counter() - t0
cluster.assert_agreement()
window = merged_log(n0)[before:]
done = len(window)
settled_tx = sum(
    sum(len(v) for v in b.contributions.values()) for b in window
)
v_window = (
    virtual_ms() - v_before if v_before is not None else None
)
m = n0.metrics
epoch_p50 = m.epoch_latency.p50
ordered = getattr(m, "ordered_latency", None)
ordered_p50 = ordered.p50 if ordered is not None else None
lag = getattr(m, "settle_lag_latency", None)
lag_p95 = lag.p95 if lag is not None else None
print(json.dumps({
    # per-epoch cadence over the chained run (wall / epochs): the
    # throughput number a paired ratio compares (merged slots when
    # the tree shards into lanes)
    "epoch_wall_ms": round(elapsed * 1000.0 / max(1, done), 3),
    "elapsed_ms": round(elapsed * 1000.0, 3),
    "epochs": done,
    "settled_tx": settled_tx,
    # wall microseconds per settled tx (per-unit cost: comparable
    # across arms even when lane count scales the submitted mass)
    "tx_wall_us": (
        round(elapsed * 1e6 / settled_tx, 3) if settled_tx else None
    ),
    # virtual (link-model) microseconds per settled tx — only when a
    # wan_profile override mounted the clock; the ISSUE-20 headline
    "tx_virtual_us": (
        round(v_window * 1000.0 / settled_tx, 3)
        if v_window and settled_tx
        else None
    ),
    # per-epoch propose -> commit p50 from the node metrics (the
    # latency number; on two-frontier code this is the SETTLED p50)
    "epoch_p50_ms": (
        round(epoch_p50 * 1000.0, 3) if epoch_p50 is not None else None
    ),
    "ordered_epoch_p50_ms": (
        round(ordered_p50 * 1000.0, 3) if ordered_p50 is not None else None
    ),
    "decrypt_lag_p95_ms": (
        round(lag_p95 * 1000.0, 3) if lag_p95 is not None else None
    ),
    # wave-routed ingest (ISSUE 10): cluster-wide batch handler
    # invocations, deterministic for the seeded schedule (null on
    # refs that predate the router)
    "handler_dispatches": (
        sum(
            hb.metrics.handler_dispatches.value
            for hb in cluster.nodes.values()
        )
        if hasattr(m, "handler_dispatches")
        else None
    ),
}))
"""


def _git(args: Sequence[str], cwd: pathlib.Path = REPO_ROOT) -> str:
    return subprocess.run(
        ["git", *args], cwd=str(cwd), check=True,
        capture_output=True, text=True,
    ).stdout.strip()


def materialize_ref(ref: str) -> pathlib.Path:
    """A detached worktree of ``ref`` under .abench/ (reused when the
    resolved commit already sits there)."""
    sha = _git(["rev-parse", "--verify", f"{ref}^{{commit}}"])
    tree = WORKTREE_DIR / sha[:12]
    if tree.exists():
        return tree
    WORKTREE_DIR.mkdir(exist_ok=True)
    _git(["worktree", "add", "--detach", str(tree), sha])
    return tree


def remove_worktree(tree: pathlib.Path) -> None:
    try:
        _git(["worktree", "remove", "--force", str(tree)])
    except subprocess.CalledProcessError:
        pass  # leave it for `git worktree prune`; never sink a report


def run_sample(
    tree: pathlib.Path,
    n: int,
    batch: int,
    epochs: int,
    seed: int,
    overrides: Optional[Dict] = None,
) -> Dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)  # each arm imports from its own tree
    if overrides:
        env["ABENCH_CONFIG_OVERRIDES"] = json.dumps(overrides)
    else:
        env.pop("ABENCH_CONFIG_OVERRIDES", None)
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE,
         str(n), str(batch), str(epochs), str(seed)],
        cwd=str(tree),
        env=env,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sample in {tree} failed (rc {proc.returncode}): "
            f"{proc.stderr.strip()[-500:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _ratio(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if (
        isinstance(a, (int, float))
        and isinstance(b, (int, float))
        and b > 0
    ):
        return round(a / b, 4)
    return None


def run_ab(
    base_ref: str,
    n: int = 16,
    batch: int = 256,
    epochs: int = 3,
    pairs: int = 4,
    seed: int = 99,
    keep_worktree: bool = False,
    progress=print,
    head_overrides: Optional[Dict] = None,
    base_overrides: Optional[Dict] = None,
) -> Dict:
    """The paired A/B: HEAD and BASE_REF sampled alternately, one
    warm-up pair discarded, ratios computed per pair.

    ``base_ref="self"`` runs BOTH arms from the working tree — the
    same-code configuration A/B (the ISSUE-15 depth comparison:
    ``--head-overrides '{"pipeline_depth":4,...}'`` vs
    ``--base-overrides '{"pipeline_depth":1}'``); per-arm Config
    kwargs ride ABENCH_CONFIG_OVERRIDES into the probe."""
    self_ab = base_ref == "self"
    base_tree = REPO_ROOT if self_ab else materialize_ref(base_ref)
    head: List[Dict] = []
    base: List[Dict] = []
    try:
        # warm-up pair (imports, JIT, page cache) — never reported
        progress(f"[abench] warm-up pair (base={base_ref})")
        run_sample(REPO_ROOT, n, batch, epochs, seed,
                   overrides=head_overrides)
        run_sample(base_tree, n, batch, epochs, seed,
                   overrides=base_overrides)
        for i in range(pairs):
            progress(f"[abench] pair {i + 1}/{pairs} head")
            head.append(
                run_sample(REPO_ROOT, n, batch, epochs, seed,
                           overrides=head_overrides)
            )
            progress(f"[abench] pair {i + 1}/{pairs} base")
            base.append(
                run_sample(base_tree, n, batch, epochs, seed,
                           overrides=base_overrides)
            )
    finally:
        if not self_ab and not keep_worktree:
            remove_worktree(base_tree)
    wall_ratios = [
        _ratio(h.get("epoch_wall_ms"), b.get("epoch_wall_ms"))
        for h, b in zip(head, base)
    ]
    p50_ratios = [
        _ratio(h.get("epoch_p50_ms"), b.get("epoch_p50_ms"))
        for h, b in zip(head, base)
    ]
    # HEAD's ordered frontier vs the base arm's (settled) epoch p50 —
    # the protocol-plane latency comparison the two-frontier split is
    # gated on (null when HEAD ran with the split off)
    ordered_ratios = [
        _ratio(h.get("ordered_epoch_p50_ms"), b.get("epoch_p50_ms"))
        for h, b in zip(head, base)
    ]
    # like-for-like ordered frontier: HEAD's ordered p50 vs the BASE
    # arm's own ordered p50 (null when the base ref predates the
    # two-frontier split) — the cleanest signal for PRs that target
    # the open->ordered window itself (delivery/routing work)
    ordered_vs_ordered = [
        _ratio(
            h.get("ordered_epoch_p50_ms"), b.get("ordered_epoch_p50_ms")
        )
        for h, b in zip(head, base)
    ]
    # per-settled-tx cost ratios (ISSUE 20): the probe saturates each
    # arm (its tx mass scales with the arm's lane count), so these
    # pair ratios compare cost per unit of settled work (< 1 = HEAD
    # cheaper per tx = higher throughput); the virtual one is
    # non-null only when a wan_profile override mounted the clock
    tx_wall_ratios = [
        _ratio(h.get("tx_wall_us"), b.get("tx_wall_us"))
        for h, b in zip(head, base)
    ]
    tx_virtual_ratios = [
        _ratio(h.get("tx_virtual_us"), b.get("tx_virtual_us"))
        for h, b in zip(head, base)
    ]

    def med(rs):
        valid = [r for r in rs if r is not None]
        return round(statistics.median(valid), 4) if valid else None

    # honesty about what the "head" arm actually ran: it samples the
    # WORKING TREE in place (uncommitted edits included), while the
    # base arm runs a clean worktree of base_ref — flag dirtiness so
    # a ratio from half-finished edits is never mistaken for HEAD's
    try:
        head_dirty = bool(_git(["status", "--porcelain"]).strip())
    except (subprocess.CalledProcessError, OSError):
        head_dirty = None  # not a git checkout: leave it unknown
    return {
        "metric": "abench_paired",
        "base_ref": base_ref,
        "head_dirty": head_dirty,
        "head_overrides": head_overrides or {},
        "base_overrides": base_overrides or {},
        "n": n,
        "batch": batch,
        "epochs": epochs,
        "seed": seed,
        "pairs": pairs,
        "head_samples": head,
        "base_samples": base,
        "pair_epoch_wall_ratios": wall_ratios,
        "pair_epoch_p50_ratios": p50_ratios,
        "pair_ordered_p50_ratios": ordered_ratios,
        "pair_ordered_vs_ordered_ratios": ordered_vs_ordered,
        "pair_tx_wall_ratios": tx_wall_ratios,
        "pair_tx_virtual_ratios": tx_virtual_ratios,
        # < 1.0 = HEAD faster, same box, same moment
        "epoch_wall_ratio_median": med(wall_ratios),
        "epoch_p50_ratio_median": med(p50_ratios),
        "ordered_p50_ratio_median": med(ordered_ratios),
        "ordered_vs_ordered_ratio_median": med(ordered_vs_ordered),
        "tx_wall_ratio_median": med(tx_wall_ratios),
        "tx_virtual_ratio_median": med(tx_virtual_ratios),
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools.abench", description=__doc__.splitlines()[0]
    )
    ap.add_argument(
        "base_ref",
        help="git ref for the base arm, or 'self' to run both arms "
        "from the working tree (configuration A/B via overrides)",
    )
    ap.add_argument(
        "--head-overrides", default=None, metavar="JSON",
        help="Config kwargs (JSON object) for the head arm, e.g. "
        '\'{"pipeline_depth": 4, "reconfig_lead": 12}\'',
    )
    ap.add_argument(
        "--base-overrides", default=None, metavar="JSON",
        help="Config kwargs (JSON object) for the base arm",
    )
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--pairs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=99)
    ap.add_argument(
        "--keep-worktree", action="store_true",
        help="leave .abench/<sha> in place for re-runs",
    )
    ap.add_argument(
        "--no-trend", action="store_true",
        help="do not append the paired report to BENCH_TREND.jsonl",
    )
    ap.add_argument(
        "--trend", default=str(REPO_ROOT / "BENCH_TREND.jsonl"),
        help="trend JSONL path the report appends to",
    )
    args = ap.parse_args(argv)
    report = run_ab(
        args.base_ref,
        n=args.n,
        batch=args.batch,
        epochs=args.epochs,
        pairs=args.pairs,
        seed=args.seed,
        keep_worktree=args.keep_worktree,
        progress=lambda msg: print(msg, file=sys.stderr, flush=True),
        head_overrides=(
            json.loads(args.head_overrides)
            if args.head_overrides
            else None
        ),
        base_overrides=(
            json.loads(args.base_overrides)
            if args.base_overrides
            else None
        ),
    )
    if not args.no_trend:
        # paired A/B reports join the durable trend: the same-box
        # ratio history is the number cross-round comparisons can
        # actually trust (the r05 cross-box lesson)
        from tools.perfgate import append_record

        record = dict(report)
        record["kind"] = "abench_paired"
        record["ts"] = _utc_stamp()
        record["fingerprint"] = {
            "kind": "abench_paired",
            "base_ref": args.base_ref,
            "n": args.n,
            "batch": args.batch,
            "epochs": args.epochs,
            "seed": args.seed,
            # configuration A/B (base_ref 'self'): the overrides ARE
            # the identity of the comparison
            "head_overrides": report["head_overrides"],
            "base_overrides": report["base_overrides"],
        }
        try:
            append_record(args.trend, record)
        except OSError:
            pass  # a report must never sink on trend bookkeeping
    print(json.dumps(report))
    return 0


def _utc_stamp() -> str:
    import time

    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


if __name__ == "__main__":
    sys.exit(main())
