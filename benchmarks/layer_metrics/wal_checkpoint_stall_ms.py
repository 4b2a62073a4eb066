"""WAL: what a checkpoint adds to the round that holds it, over the
whole window: the mean length of the rounds in which a checkpoint epoch
was stamped settled, less the median length of the window's rounds.
The harness's own stamps (``Served.round_log``, ``t_settled``); which
epochs checkpoint is the configuration's own statement
(``config.ledger_checkpoint_every``: every that many commits of a log
that started empty, so epochs every - 1, 2 * every - 1, ...).  The
profiler's part of the window is its last seconds, which as a rule hold
no checkpoint, so ``wal_pct`` and ``wal_ms_per_commit`` do not see it.
Nothing is reported where the window holds no such round."""

import statistics


def read(run):
    rounds, at = run.get("rounds"), run.get("t_settled")
    every = ((run.get("config") or {}).get("config") or {}).get(
        "ledger_checkpoint_every", 0
    )
    if not rounds or not at or every <= 0:
        return None
    t0, t_end = run["t0"], run["t_end"]
    inside = [(a, b) for a, b, _bytes in rounds if t0 <= a < t_end]
    marks = [at[e] for e in range(every - 1, len(at), every)]
    held = [b - a for a, b in inside if any(a < t <= b for t in marks)]
    if not held:
        return None
    return 1e3 * (
        statistics.fmean(held) - statistics.median(b - a for a, b in inside)
    )
