"""Fault schedule: delivery waves a round with validators down, over
delivery waves a round before the kill (medians, over the window's whole
rounds on either side).  The extra waves are the BBA's extra rounds:
every instance of a dead proposer has to decide 0."""

import statistics

from benchmarks.layer_metrics._faults import degraded, report


def read(run):
    span = degraded(run)
    rounds = run.get("rounds")
    if span is None or not rounds:
        return None
    t_kill, t_restart = span
    waves = report(run)["round_waves"]
    before = [w for (a, b, _), w in zip(rounds, waves)
              if run["t0"] <= a and b <= t_kill]
    during = [w for (a, b, _), w in zip(rounds, waves)
              if t_kill <= a and b <= t_restart]
    if not before or not during:
        return None
    return statistics.median(during) / statistics.median(before)
