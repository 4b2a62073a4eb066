"""Shared by the CATCHUP readers: the spans ``protocol/honeybadger.py``
puts around state transfer after a restart (``catchup/request``: one
broadcast asking the roster from a frontier on; ``catchup/serve`` and
``catchup/serve_settled``: one window answered or pushed, and beneath
them ``catchup/serve_body``: one batch body encoded and sent;
``catchup/adopt``: one batch taken over on f+1 identical bodies,
``catchup/adopt_ordered``: one ordering), and ``ledger/replay``, which
``core/ledger.py`` puts around each read of a log (open, validate every
record) at a restart.  A run with no fault schedule has no restart, and
a program without these spans (before PR 37) gives none: both read
None."""

from benchmarks.layer_metrics._faults import report
from benchmarks.layer_metrics._spans import totals

SPANS = ("catchup/", "ledger/replay")


def table(run):
    """The rows of ``trace.totals()`` among ``SPANS``, or None."""
    if report(run) is None:
        return None
    rows = {
        name: row for name, row in (totals(run) or {}).items()
        if name.startswith(SPANS)
    }
    return rows or None
