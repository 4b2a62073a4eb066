"""Fault schedule: transactions the clients sent again at the kill:
acknowledged only by validators that were killed, and settled in no
epoch stamped by then.  Each stays one attempt, timed from its first
due time, so they are the requests that feel the outage most."""

from benchmarks.layer_metrics._faults import report


def read(run):
    faults = report(run)
    if faults is None:
        return None
    return float(faults["resubmitted"])
