"""Fault schedule: mean time of one ``SimulatedCluster.restart_node``
call, on the harness's clock: a fresh ``HoneyBadger`` built from the
validator's log (the last checkpoint and the batch records after it).
The per-validator figure: in the one-process fold the replays run one
after another, in a deployment each on its own host."""

import statistics

from benchmarks.layer_metrics._faults import report


def read(run):
    replays = [o["replay_s"] for o in (report(run) or {}).get(
        "outages", ()) if "replay_s" in o]
    if not replays:
        return None
    return 1e3 * statistics.fmean(replays)
