"""Fault schedule: batch bodies served over batches adopted, in the
traced part: the calls of ``catchup/serve_body`` (one a body a peer
encodes and sends) over those of ``catchup/adopt`` (one an epoch a
restarted validator takes over).  Every peer asked answers, so the
floor is the number of responders (eleven in the cells with five down);
what lies above it is ranges served again (a requester asks anew after
every adoption, a peer answers a range it has served ``CATCHUP_REPEAT_
BUDGET`` times more)."""

from benchmarks.layer_metrics._catchup import table


def read(run):
    rows = table(run)
    if rows is None:
        return None
    served = rows.get("catchup/serve_body", {}).get("calls", 0)
    adopted = rows.get("catchup/adopt", {}).get("calls", 0)
    if not served or not adopted:
        return None
    return served / adopted
