"""Fault schedule: from a restart event to the moment the last
validator it restarted is in service again (its settled frontier level
with the others'): the replays from the logs, one after another in the
one-process fold, and the catching up; the longest where the schedule
restarts more than once.  Nothing while one is still catching up.  It
is one event a run and swings by a fifth from run to run, so it stands
here and not under a bound (PERF.md section 2)."""

from benchmarks.layer_metrics._faults import report


def read(run):
    faults = report(run)
    if faults is None or faults["never_back"]:
        return None
    back = [o["t_in_service"] - o["t_restart_event"]
            for o in faults["outages"] if "t_in_service" in o]
    return max(back) if back else None
