"""Fault schedule: time without service with validators down.  The
longest interval between two settle stamps in a row that both lie
between the kill and the restart, less the median such interval before
the kill.  A leaderless protocol with f of N down should read about
nought: no view change, no election; what it reads is a degraded
round that ran long.  (The stall of the restart itself starts where
this stretch ends: ``recover_s`` and the tails read that.)"""

import statistics

from benchmarks.layer_metrics._faults import degraded


def read(run):
    span = degraded(run)
    if span is None:
        return None
    t_kill, t_restart = span
    stamps = sorted({t for t in run["t_settled"] if t >= run["t0"]})
    gaps = list(zip(stamps, stamps[1:]))
    before = [b - a for a, b in gaps if b <= t_kill]
    during = [b - a for a, b in gaps if t_kill <= a and b <= t_restart]
    if not before or not during:
        return None
    return 1e3 * (max(during) - statistics.median(before))
