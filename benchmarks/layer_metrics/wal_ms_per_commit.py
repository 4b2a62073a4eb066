"""WAL: what one validator spends on its log an epoch: the total time
of the log's three spans over the traced part, divided by the batch
records appended there (``ledger/wal_append`` is entered once a
validator and settled epoch, so its calls are validators x epochs)."""

from benchmarks.layer_metrics._spans import totals
from benchmarks.layer_metrics._wal import SPANS


def read(run):
    table = totals(run)
    if table is None:
        return None
    commits = table.get("ledger/wal_append", {}).get("calls", 0)
    if not commits:
        return None
    seconds = sum(table[name]["total_s"] for name in SPANS if name in table)
    return 1e3 * seconds / commits
