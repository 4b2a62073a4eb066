"""Python collector: ``gc_pct`` (self time of every ``gc/*`` span, share
of the traced window) for the cells with a fault schedule, where it moves
``settle_p99_ms``: the window traced there runs from before the restart
to the drain's end."""

from benchmarks.layer_metrics.gc_pct import read  # noqa: F401
