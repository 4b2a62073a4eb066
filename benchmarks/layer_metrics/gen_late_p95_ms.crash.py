"""Load generator: ``gen_late_p95_ms`` (how late the harness submitted,
95th percentile of submitted - due over every transaction due in the
window) for the cells with a fault schedule.  The twin's reader moves
``settle_p90_ms``, which such a cell does not report (PERF.md section
2); this one moves ``settle_p99_ms``, the tail it does.  The replays of
a restart run on the generator's own thread, so what was due during
them is submitted when they end; its latency runs from its due time all
the same."""

from benchmarks.layer_metrics.gen_late_p95_ms import read  # noqa: F401
