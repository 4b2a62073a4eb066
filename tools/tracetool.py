"""tracetool: merge, validate and explain flight-recorder artifacts.

The recorder half lives in `cleisthenes_tpu/utils/trace.py` (per-node
bounded rings, merged into one Chrome-trace-event JSON by
`to_chrome`); this tool is the analysis half:

- ``--validate``  schema gate: every event carries a known category,
  a name, timestamps, and a per-track ``seq`` that increases strictly
  monotonically (sequence numbers are the determinism-plane ordering
  truth; timestamps are observability-only).  The ci.sh observability
  stage pipes a freshly captured seeded-cluster artifact through this.
- ``--report`` (default)  per-epoch critical-path attribution: the
  wall time from the earliest ``epoch/open`` to the latest
  ``epoch/commit`` is tiled by the merged event timeline — each gap is
  attributed to the stage (category) of the event that TERMINATES it,
  which in the serialized in-proc cluster is literally "what the run
  was computing toward next".  Prints per-epoch stage shares, the
  longest chain segments, and a summary table (hub dispatch counts by
  class, wave sizes, p50/p95 span durations).
- ``--capture OUT``  runs a seeded N-node SimulatedCluster with
  tracing on and writes the merged artifact — the self-contained
  source of CI fixtures and quick local looks.
- ``--device-gaps DIR [--window lockstep/epoch]``  reads a JAX
  profiler directory (``jax.profiler.start_trace(DIR)`` around the
  run): the program's spans (``utils.trace.span``) lie on the host
  plane of the same xplane as the device's "XLA Modules" line, so the
  device's idle time splits by the innermost program span that covers
  it.  Prints the device programs and that split, with
  ``benchmarks/trace_reduce.py``'s own reduction.

Open artifacts interactively at https://ui.perfetto.dev ("Open trace
file"); one track per node, spans nested by category.  Schema details:
docs/TRACING.md.
"""

from __future__ import annotations

import argparse
import bisect
import json
import operator
import pathlib
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from cleisthenes_tpu.utils.trace import CATEGORIES  # noqa: E402

_ALLOWED_PH = frozenset(("M", "X", "i"))


# ---------------------------------------------------------------------------
# loading & validation
# ---------------------------------------------------------------------------


def load(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def track_names(doc: dict) -> Dict[int, str]:
    """tid -> node name from the thread_name metadata events."""
    out: Dict[int, str] = {}
    for ev in doc.get("traceEvents", ()):
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            out[ev.get("tid", 0)] = str(ev.get("args", {}).get("name", ""))
    return out


def validate(doc: dict) -> List[str]:
    """Schema + per-track monotone-sequence check; [] means valid."""
    errors: List[str] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["artifact has no traceEvents list"]
    if not events:
        return ["traceEvents is empty"]
    last_seq: Dict[int, int] = {}
    names = track_names(doc)
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _ALLOWED_PH:
            errors.append(f"{where}: unknown ph {ph!r}")
            continue
        if ph == "M":
            continue
        cat = ev.get("cat")
        if cat not in CATEGORIES:
            errors.append(f"{where}: unknown category {cat!r}")
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errors.append(f"{where}: missing event name")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errors.append(f"{where}: bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where}: X event with bad dur {dur!r}")
        args = ev.get("args")
        if not isinstance(args, dict):
            errors.append(f"{where}: missing args")
            continue
        seq = args.get("seq")
        tid = ev.get("tid")
        if not isinstance(seq, int) or seq < 1:
            errors.append(f"{where}: bad args.seq {seq!r}")
            continue
        if tid in last_seq and seq <= last_seq[tid]:
            node = names.get(tid, tid)
            errors.append(
                f"{where}: seq {seq} not after {last_seq[tid]} on "
                f"track {node!r} (per-node sequence must be "
                "strictly increasing)"
            )
        last_seq[tid] = seq
    return errors


# ---------------------------------------------------------------------------
# per-epoch critical-path attribution
# ---------------------------------------------------------------------------


def _analysis_events(doc: dict) -> List[dict]:
    return [
        ev
        for ev in doc.get("traceEvents", ())
        if ev.get("ph") in ("X", "i")
    ]


def _point(ev: dict) -> float:
    """The instant an event 'happened': span END for X events (when
    the work finished), ts for instants."""
    return float(ev["ts"]) + float(ev.get("dur", 0.0))


def epoch_windows(doc: dict) -> Dict[Tuple[int, int], Tuple[float, float]]:
    """(lane, epoch) -> (us of earliest open, us of latest close),
    for every epoch with both markers.  Lane-sharded artifacts
    (Config.lanes > 1) tag epoch events with a ``lane`` arg; lanes
    reuse epoch numbers, so the key must carry the lane or the
    windows of S concurrent epoch-k runs would merge into one bogus
    span.  Single-lane artifacts carry no ``lane`` arg and key as
    lane 0 — the historical window set, unchanged.

    The closing marker is the latest ``epoch/ordered`` instant when
    the artifact carries one for that epoch (the two-frontier commit
    split, Config.order_then_settle: the protocol-plane epoch ENDS at
    the ciphertext-ordered commit; decryption trails on the settle
    track, visible as the ``settle/decrypt_lag`` spans outside these
    windows), falling back to the latest ``epoch/commit`` on coupled
    artifacts."""
    opens: Dict[Tuple[int, int], float] = {}
    commits: Dict[Tuple[int, int], float] = {}
    ordereds: Dict[Tuple[int, int], float] = {}
    for ev in _analysis_events(doc):
        if ev.get("cat") != "epoch":
            continue
        args = ev.get("args", {})
        epoch = args.get("epoch")
        if not isinstance(epoch, int):
            continue
        key = (int(args.get("lane", 0)), epoch)
        ts = float(ev["ts"])
        if ev["name"] == "open":
            if key not in opens or ts < opens[key]:
                opens[key] = ts
        elif ev["name"] == "commit":
            if key not in commits or ts > commits[key]:
                commits[key] = ts
        elif ev["name"] == "ordered":
            if key not in ordereds or ts > ordereds[key]:
                ordereds[key] = ts
    closes = {**commits, **ordereds}  # ordered wins where present
    return {
        k: (opens[k], closes[k])
        for k in sorted(opens)
        if k in closes and closes[k] > opens[k]
    }


def sorted_points(doc: dict) -> List[Tuple[float, str, str, int]]:
    """All event completion points (point_us, cat, name, tid), sorted
    once — epoch windows slice into this via bisect, so analyzing E
    (possibly overlapping, under pipelining) epochs costs one sort,
    not E re-sorts of the whole artifact."""
    return sorted(
        (
            (_point(ev), ev["cat"], ev["name"], ev.get("tid", 0))
            for ev in _analysis_events(doc)
        ),
        key=operator.itemgetter(0),
    )


def attribute_epoch(
    doc: dict,
    t_open: float,
    t_commit: float,
    points: Optional[List[Tuple[float, str, str, int]]] = None,
) -> Tuple[Dict[str, float], List[Tuple[float, str, str, int]]]:
    """Tile [t_open, t_commit] by the merged timeline.

    Returns (shares, chain): ``shares`` maps category -> attributed
    microseconds (summing to exactly the window — every gap ends at
    some recorded event, and the closing commit is itself an event);
    ``chain`` is the gap list (gap_us, cat, name, tid) in time order —
    its largest entries are the epoch's critical-path segments.

    ``points`` is the precomputed ``sorted_points(doc)`` list; pass it
    when analyzing many windows of one artifact.
    """
    if points is None:
        points = sorted_points(doc)
    key = operator.itemgetter(0)
    lo = bisect.bisect_right(points, t_open, key=key)
    hi = bisect.bisect_right(points, t_commit, key=key)
    shares: Dict[str, float] = {}
    chain: List[Tuple[float, str, str, int]] = []
    prev = t_open
    for point, cat, name, tid in points[lo:hi]:
        gap = point - prev
        if gap > 0:
            shares[cat] = shares.get(cat, 0.0) + gap
            chain.append((gap, cat, name, tid))
        prev = point
    # anything after the last recorded point (can only happen in a
    # degenerate artifact where commit was dropped by ring overflow)
    tail = t_commit - prev
    if tail > 0:
        shares["epoch"] = shares.get("epoch", 0.0) + tail
        chain.append((tail, "epoch", "(untraced tail)", 0))
    return shares, chain


def stage_shares(doc: dict) -> Dict[str, float]:
    """Whole-run per-stage fractions of total epoch wall time — the
    bench.py --trace breakdown (fractions sum to ~1.0)."""
    windows = epoch_windows(doc)
    points = sorted_points(doc)
    totals: Dict[str, float] = {}
    wall = 0.0
    for t_open, t_commit in windows.values():
        shares, _chain = attribute_epoch(doc, t_open, t_commit, points)
        for cat, us in shares.items():
            totals[cat] = totals.get(cat, 0.0) + us
        wall += t_commit - t_open
    if wall <= 0:
        return {}
    return {
        cat: round(us / wall, 4) for cat, us in sorted(totals.items())
    }


# ---------------------------------------------------------------------------
# summary tables
# ---------------------------------------------------------------------------


def _percentile(values: List[float], p: float) -> Optional[float]:
    if not values:
        return None
    vs = sorted(values)
    idx = min(len(vs) - 1, int(round((p / 100.0) * (len(vs) - 1))))
    return vs[idx]


def summarize(doc: dict) -> dict:
    """Counts + distributions: hub dispatch classes, wave sizes,
    span-duration percentiles, event counts by category."""
    by_cat: Dict[str, int] = {}
    span_durs: Dict[Tuple[str, str], List[float]] = {}
    wave_sizes: List[float] = []
    hub = {"flushes": 0, "dispatches": 0, "branches": 0, "decodes": 0,
           "shares": 0}
    # delivery-plane columnarization (ISSUE 9): frame_decode spans
    # carry memo_hit, mac_verify_batch spans carry batch_width — the
    # counters a critical-path capture needs to attribute the
    # delivery-plane delta
    delivery = {
        "frame_decodes": 0,
        "decode_memo_hits": 0,
        "mac_verify_batches": 0,
        # wave-routed ingest (ISSUE 10): one router/route span per
        # delivery wave; args carry the wave's payload count and the
        # batch handler dispatches it collapsed to
        "router_waves": 0,
        "router_payloads": 0,
        "router_dispatches": 0,
        # egress columnarization (ISSUE 13): one transport/frame_encode
        # span per egress wave (args: bundle count + encode-memo hits)
        # and one coin/share_batch span per native coin-issue dispatch
        # (args: items + distinct owners) — the send-side twins
        "frame_encode_waves": 0,
        "frame_encode_bundles": 0,
        "encode_memo_hits": 0,
        "coin_share_batches": 0,
        "coin_share_items": 0,
    }
    batch_widths: List[float] = []
    # lane shard-out (ISSUE 20): epoch events on lane-sharded
    # artifacts carry a ``lane`` arg; merge/emit instants mark the
    # total-order slots the cross-lane merge released
    lane_ordered: Dict[int, int] = {}
    merge_emits = 0
    for ev in _analysis_events(doc):
        cat = ev["cat"]
        by_cat[cat] = by_cat.get(cat, 0) + 1
        if ev["ph"] == "X":
            span_durs.setdefault((cat, ev["name"]), []).append(
                float(ev.get("dur", 0.0))
            )
        args = ev.get("args", {})
        if cat == "epoch" and ev["name"] in ("ordered", "commit"):
            lane = int(args.get("lane", 0))
            lane_ordered[lane] = lane_ordered.get(lane, 0) + 1
        elif cat == "merge" and ev["name"] == "emit":
            merge_emits += 1
        if cat == "hub" and ev["name"] == "flush":
            hub["flushes"] += 1
            for k in ("dispatches", "branches", "decodes", "shares"):
                hub[k] += int(args.get(k, 0))
        elif cat == "transport" and ev["name"] in ("wave", "queue_depth"):
            msgs = args.get("msgs")
            if isinstance(msgs, (int, float)):
                wave_sizes.append(float(msgs))
        elif cat == "transport" and ev["name"] == "frame_decode":
            # one span covers one prepare-wave's decode attempts for
            # one receiver; args carry the counts
            delivery["frame_decodes"] += int(args.get("frames", 1))
            delivery["decode_memo_hits"] += int(args.get("memo_hits", 0))
        elif cat == "transport" and ev["name"] == "mac_verify_batch":
            delivery["mac_verify_batches"] += 1
            width = args.get("batch_width")
            if isinstance(width, (int, float)):
                batch_widths.append(float(width))
        elif cat == "transport" and ev["name"] == "frame_encode":
            # NOTE: "bundles" (folded envelopes per wave) is a
            # different unit than the metrics counter frames_encoded
            # (payload BODIES actually encoded) — named apart so a
            # trace report is never cross-read as that counter
            delivery["frame_encode_waves"] += 1
            delivery["frame_encode_bundles"] += int(args.get("frames", 1))
            delivery["encode_memo_hits"] += int(args.get("memo_hits", 0))
        elif cat == "coin" and ev["name"] == "share_batch":
            delivery["coin_share_batches"] += 1
            delivery["coin_share_items"] += int(args.get("n", 0))
        elif cat == "router" and ev["name"] == "route":
            delivery["router_waves"] += 1
            delivery["router_payloads"] += int(args.get("payloads", 0))
            delivery["router_dispatches"] += int(
                args.get("dispatches", 0)
            )
    spans = {
        f"{cat}/{name}": {
            "n": len(durs),
            "p50_us": round(_percentile(durs, 50), 1),
            "p95_us": round(_percentile(durs, 95), 1),
        }
        for (cat, name), durs in sorted(span_durs.items())
    }
    delivery["mac_batch_width_p50"] = _percentile(batch_widths, 50)
    delivery["mac_batch_width_p95"] = _percentile(batch_widths, 95)
    return {
        "events_by_category": dict(sorted(by_cat.items())),
        "hub": hub,
        "delivery": delivery,
        "lanes": {
            "count": (max(lane_ordered) + 1) if lane_ordered else 1,
            "ordered_by_lane": dict(sorted(lane_ordered.items())),
            "merge_emits": merge_emits,
        },
        "wave_size_p50": _percentile(wave_sizes, 50),
        "wave_size_p95": _percentile(wave_sizes, 95),
        "spans": spans,
    }


def report(doc: dict, top: int = 5) -> str:
    """The human-readable critical-path report."""
    names = track_names(doc)
    lines: List[str] = []
    windows = epoch_windows(doc)
    points = sorted_points(doc)
    if not windows:
        lines.append("no complete epochs (open+commit) in the artifact")
    for (lane, epoch), (t_open, t_commit) in windows.items():
        wall = t_commit - t_open
        shares, chain = attribute_epoch(doc, t_open, t_commit, points)
        covered = sum(shares.values())
        label = f"epoch {epoch}" if lane == 0 else f"epoch {epoch} lane {lane}"
        lines.append(
            f"{label}: wall {wall / 1000.0:.3f} ms, "
            f"{100.0 * covered / wall:.1f}% attributed"
        )
        for cat, us in sorted(
            shares.items(), key=lambda kv: -kv[1]
        ):
            lines.append(
                f"  {cat:<10} {us / 1000.0:>10.3f} ms "
                f"({100.0 * us / wall:5.1f}%)"
            )
        lines.append("  critical-path segments (longest first):")
        for gap, cat, name, tid in sorted(chain, key=lambda c: -c[0])[
            :top
        ]:
            lines.append(
                f"    {gap / 1000.0:>9.3f} ms -> {cat}/{name} "
                f"@ {names.get(tid, tid)}"
            )
    s = summarize(doc)
    lines.append("summary:")
    lines.append(f"  events by category: {s['events_by_category']}")
    lines.append(f"  hub: {s['hub']}")
    lines.append(f"  delivery: {s['delivery']}")
    if s["lanes"]["count"] > 1:
        lines.append(f"  lanes: {s['lanes']}")
    lines.append(
        f"  wave size p50/p95: {s['wave_size_p50']}/{s['wave_size_p95']}"
    )
    for span, st in s["spans"].items():
        lines.append(
            f"  span {span:<22} n={st['n']:<5} "
            f"p50={st['p50_us']}us p95={st['p95_us']}us"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# capture: a seeded traced cluster in one command (the CI fixture)
# ---------------------------------------------------------------------------


def capture(
    out_path: str,
    n: int = 4,
    seed: int = 7,
    txs: int = 24,
    batch: int = 8,
) -> dict:
    """Run a seeded N-node SimulatedCluster with tracing on, write the
    merged artifact, and return the loaded document."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    cluster = SimulatedCluster(
        config=Config(n=n, batch_size=batch, seed=seed, trace=True),
        seed=seed,
        key_seed=1,
    )
    for i in range(txs):
        cluster.submit(b"trace-tx-%04d" % i)
    cluster.run_epochs()
    cluster.assert_agreement()
    cluster.write_trace(out_path)
    return load(out_path)


# ---------------------------------------------------------------------------
# program spans beside the device: the profiler's xplane
# ---------------------------------------------------------------------------


def _host_events(log_dir: str):
    """The host planes' events of the newest profile under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from line.events


def profile_span_names(log_dir: str) -> set:
    """Every host annotation of the newest profile under ``log_dir``
    that is a program span: ``cat/name`` with a known category."""
    return {
        ev.name
        for ev in _host_events(log_dir)
        if "/" in ev.name and ev.name.split("/", 1)[0] in CATEGORIES
    }


def share_tally(log_dir: str) -> Dict[str, int]:
    """``ops.tpke.share_tally()`` as the profile saw it: shares issued
    as byte columns and as lists (the ``items`` and ``columnar`` args
    of ``tpke/issue_batch``) and ``DhShare`` objects made inside the
    two batch spans (their ``materialized`` args)."""
    out = {
        "shares_issued_columnar": 0,
        "shares_issued_listed": 0,
        "shares_materialized": 0,
    }
    for ev in _host_events(log_dir):
        if ev.name not in ("tpke/issue_batch", "tpke/verify_combine_batch"):
            continue
        args = dict(ev.stats)
        out["shares_materialized"] += int(args.get("materialized", 0))
        if ev.name == "tpke/issue_batch":
            how = "columnar" if args.get("columnar") else "listed"
            out["shares_issued_" + how] += int(args.get("items", 0))
    return out


def device_gaps(log_dir: str, window: Optional[str] = None) -> dict:
    """``trace_reduce.reduce`` of the profile with the program's spans
    kept beside the harness's annotations.  ``window`` names the
    annotation whose first start and last end bound the reduction
    (default: the harness's ``traced_window``)."""
    from benchmarks import trace_reduce
    from benchmarks.run import SPAN_NAMES

    keep = profile_span_names(log_dir) | set(SPAN_NAMES)
    trace = trace_reduce.load_xplane(log_dir, keep)
    reduced = trace_reduce.reduce(trace, window or SPAN_NAMES[0])
    reduced["share_tally"] = share_tally(log_dir)
    return reduced


def device_gaps_report(reduced: dict) -> str:
    window, busy = reduced["window_s"], reduced["busy_s"]
    lines = [
        f"window {window:.6f} s, device busy {busy:.6f} s "
        f"({100 * busy / window:.1f}%), {reduced['devices']} device(s)"
        + (", TRACE BUFFERS DROPPED" if reduced["dropped"] else ""),
        "device programs (s):",
    ]
    lines += [f"  {s:12.6f}  {name}" for name, s in reduced["device_ops"]]
    lines.append("device idle by innermost span, ten longest (s):")
    lines += [f"  {s:12.6f}  {name}" for name, s in reduced["idle_gaps"]]
    rest = window - busy - sum(s for _name, s in reduced["idle_gaps"])
    lines.append(f"  {max(rest, 0.0):12.6f}  (every other span)")
    tally = reduced.get("share_tally")
    if tally and any(tally.values()):
        lines.append(
            "threshold shares in the profile: "
            + ", ".join(f"{k[7:]} {v}" for k, v in tally.items())
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools.tracetool")
    ap.add_argument(
        "artifact",
        nargs="?",
        help="merged Chrome-trace JSON (from SimulatedCluster."
        "write_trace, demo.py --trace, or --capture)",
    )
    ap.add_argument(
        "--validate",
        action="store_true",
        help="schema + per-track monotone-seq gate (exit 1 on errors)",
    )
    ap.add_argument(
        "--report",
        action="store_true",
        help="critical-path + summary report (the default action)",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="emit stage shares + summary as one JSON object",
    )
    ap.add_argument(
        "--capture",
        metavar="OUT",
        help="run a seeded traced cluster and write the artifact here",
    )
    ap.add_argument(
        "--device-gaps",
        metavar="DIR",
        help="JAX profiler directory: device programs and the device's "
        "idle time by innermost program span",
    )
    ap.add_argument(
        "--window",
        help="with --device-gaps: the span that bounds the reduction "
        "(e.g. lockstep/epoch; default the harness's traced_window)",
    )
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--txs", type=int, default=24)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args(argv)

    if args.device_gaps:
        print(device_gaps_report(device_gaps(args.device_gaps, args.window)))
        return 0
    if args.capture:
        doc = capture(
            args.capture,
            n=args.n,
            seed=args.seed,
            txs=args.txs,
            batch=args.batch,
        )
        n_events = sum(1 for _ in _analysis_events(doc))
        print(
            f"tracetool: captured {n_events} events from a seeded "
            f"{args.n}-node cluster -> {args.capture}"
        )
        return 0
    if not args.artifact:
        ap.error("need an artifact path (or --capture OUT)")
    doc = load(args.artifact)
    if args.validate:
        errors = validate(doc)
        for e in errors:
            print(e)
        n_events = sum(1 for _ in _analysis_events(doc))
        print(
            f"tracetool: {n_events} events, {len(errors)} schema "
            f"problem(s)"
        )
        return 1 if errors else 0
    if args.json:
        print(
            json.dumps(
                {
                    "stage_shares": stage_shares(doc),
                    "summary": summarize(doc),
                }
            )
        )
        return 0
    print(report(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
