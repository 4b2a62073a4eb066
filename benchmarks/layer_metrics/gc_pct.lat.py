"""Python collector: ``gc_pct`` (self time of every ``gc/*`` span, share
of the traced window) for the open-loop cells, where it moves
``settle_p90_ms``."""

from benchmarks.layer_metrics.gc_pct import read  # noqa: F401
