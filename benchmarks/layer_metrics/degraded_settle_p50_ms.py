"""Fault schedule: median due -> settled of the transactions that were
due while validators were down (between the kill and the restart); one
that never settled is beyond every percentile.  Beside the window's
``settle_p50_ms`` it says what the outage itself costs a client: the
BBA instances of the dead proposers have to decide 0, which takes the
coin more rounds than a healthy epoch's."""

from benchmarks.layer_metrics._faults import degraded, settle_pctl_ms


def read(run):
    span = degraded(run)
    if span is None or not run.get("due"):
        return None
    return settle_pctl_ms(run, 0.50, *span)
