"""Python collector: the self time of every ``gc/*`` span (a collection
of generation 0, 1 or 2), share of the traced window."""

from benchmarks.layer_metrics._gc import pct


def read(run):
    return pct(run)
