"""Lockstep executor: ``run_epoch``'s own Python (item tuples, toss,
branch assembly), the self time of ``lockstep/*``, share of the traced
window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "lockstep/")
