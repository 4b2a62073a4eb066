"""XLA compile: compilations inside the window and its drain (there
should be none), for the cells with a fault schedule.  The twin's
``compiles_in_window.lat`` moves ``settle_p90_ms``, which such a cell
does not report (PERF.md section 2); this one moves ``settle_p99_ms``,
the tail it does."""

from benchmarks.layer_metrics._delta import compiles


def read(run):
    return compiles(run)
