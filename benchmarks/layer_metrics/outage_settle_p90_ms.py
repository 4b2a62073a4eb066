"""Fault schedule: the window's 90th percentile of due -> settled, over
every transaction due in it, as ``settle_p90_ms`` is read.  In a cell
with an outage it lies inside the share of transactions that wait
behind the restart's stall and the backlog after it, and swings with
the length of that one stall by more than a bound admits, so it stands
here, beside ``settle_p99_ms`` which is under one (PERF.md section 2)."""

from benchmarks.layer_metrics._faults import report, settle_pctl_ms


def read(run):
    if report(run) is None or not run.get("due"):
        return None
    return settle_pctl_ms(run, 0.90)
