"""Shared by the collector's readers: the rows ``gc/gen0``, ``gc/gen1``
and ``gc/gen2`` of the program's span totals.  ``utils/trace.py`` opens
one such span a run of Python's cyclic collector while the profiler's
session runs, beneath the span whose allocation set it off, so its time
is no other span's self time.  A program without these spans has no
``gc/`` row and reads None, where a 0 would say "no collection"."""

from benchmarks.layer_metrics._spans import totals


def rows(run):
    """The ``gc/*`` rows of ``trace.totals()``, or None."""
    found = {
        name: row for name, row in (totals(run) or {}).items()
        if name.startswith("gc/")
    }
    return found or None


def pct(run):
    """Their self time over the traced window, in %."""
    found = rows(run)
    window = (run.get("trace") or {}).get("window_s")
    if found is None or not window:
        return None
    return 100.0 * sum(row["self_s"] for row in found.values()) / window
