"""WAL: the self time of the log's three spans (write, flush and, where
the configuration syncs, the sync), share of the traced window.  All
sixteen validators' appends run in turn on the one thread, so this is
the fold's share of a round, up to sixteen times one validator's own
(the configuration's ``reduced.wal_disks``)."""

from benchmarks.layer_metrics._spans import window_pct
from benchmarks.layer_metrics._wal import SPANS


def read(run):
    return window_pct(run, "self_s", *SPANS)
