"""Shared by the two Reed-Solomon roofline readers: device seconds of
the named jitted programs ("XLA Modules", as modexp256_roofline reads
them) over the traced part of the window, against the least time for
the work the placement tally counted over the same part
(benchmarks/work_erasure.py).  None where no such program ran, or the
tally has no byte counts (a program from before they were added)."""

from benchmarks.layer_metrics._delta import placement_delta
from benchmarks.peaks import peaks_for
from benchmarks.work_erasure import roofline_pct


def geometry(run):
    """(n, k) of the configuration's erasure code: k = n - 2f, with
    f = floor((n - 1) / 3) unless the configuration states one."""
    cfg = run["config"]["config"]
    n = int(cfg["n"])
    f = int(cfg.get("f", (n - 1) // 3))
    return n, n - 2 * f


def roofline(run, programs, work_of):
    trace = run.get("trace")
    ends = run["counters"].get("trace") or {}
    if not trace or "before" not in ends or "after" not in ends:
        return None
    seconds = sum(trace["programs"].get(p, 0.0) for p in programs)
    delta = placement_delta(
        ends["before"]["placement"], ends["after"]["placement"]
    )
    work = work_of(delta, *geometry(run))
    if seconds <= 0.0 or work is None or work["in_bytes"] == 0:
        return None
    return roofline_pct(work, seconds, peaks_for(run["device_kind"]))
