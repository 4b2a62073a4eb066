"""Python collector: full collections (calls of ``gc/gen2``) over the
epochs settled between the trace's two counter snapshots.  0 where the
collector ran only on the younger generations."""

from benchmarks.layer_metrics._gc import rows


def read(run):
    found = rows(run)
    ends = run["counters"].get("trace") or {}
    if found is None or "before" not in ends or "after" not in ends:
        return None
    epochs = ends["after"]["epochs"] - ends["before"]["epochs"]
    if epochs <= 0:
        return None
    return found.get("gc/gen2", {}).get("calls", 0) / epochs
