"""Fault schedule: served rounds from a restart until every validator
it restarted is in service again (the round of the restart counts as
the first; the longest, where the schedule restarts more than once).
Nothing is reported while one is still catching up."""

from benchmarks.layer_metrics._faults import report


def read(run):
    faults = report(run)
    if faults is None or faults["never_back"]:
        return None
    back = [o["round_in_service"] - o["round_restart"] + 1
            for o in faults["outages"] if "round_in_service" in o]
    if not back:
        return None
    return float(max(back))
