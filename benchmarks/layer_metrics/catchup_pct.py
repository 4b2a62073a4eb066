"""Fault schedule: the self time of the CATCHUP plane's spans and of the
log's replay, share of the traced window, which in a cell with a fault
schedule starts before the restart and runs to the drain's end.  Five
restarted validators' reads, requests and adoptions and the eleven
others' serves run in turn on the one thread (the configuration's
``reduced.fold``).  Decoding the frames that carry the bodies and
routing them to the handler read as ``transport/*`` and
``router/route``, not here."""

from benchmarks.layer_metrics._catchup import table


def read(run):
    rows = table(run)
    window = (run.get("trace") or {}).get("window_s")
    if rows is None or not window:
        return None
    return 100.0 * sum(row["self_s"] for row in rows.values()) / window
