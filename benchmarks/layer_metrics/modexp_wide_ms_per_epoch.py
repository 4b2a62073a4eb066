"""ops/ seam: the 2048-bit crypto plane's milliseconds an epoch — the
total time of the ``ops/modexp_11x192.*`` spans (one a batch of the
MODP-14 group's exponentiations through the 'tpu' engine, on the device
or on the host kernel the floor sent it to) over the epochs settled
between the trace's two counter snapshots.  A floor that moves the
batches reads on the same yardstick.  None where no such span ran."""

from benchmarks.layer_metrics._spans import totals

PREFIX = "ops/modexp_11x192."


def read(run):
    table = totals(run)
    ends = run["counters"].get("trace") or {}
    if table is None or "before" not in ends or "after" not in ends:
        return None
    rows = [row for name, row in table.items() if name.startswith(PREFIX)]
    epochs = ends["after"]["epochs"] - ends["before"]["epochs"]
    if not rows or epochs <= 0:
        return None
    return 1e3 * sum(row["total_s"] for row in rows) / epochs
