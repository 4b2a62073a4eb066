"""The one reading of a served window's latencies: run.py's end-to-end
percentiles and the per-layer readers that cut the same sample by due
time (layer_metrics/degraded_settle_p50_ms.py, outage_settle_p90_ms.py)
both take it from here."""

from __future__ import annotations

import math
from typing import Dict, List, Optional


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample
    at or below it; infinite of an empty sample."""
    if not sorted_vals:
        return math.inf
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


def latencies_ms(run: Dict, stamps: str, since: Optional[float] = None,
                 until: Optional[float] = None) -> List[float]:
    """Sorted, in ms: due time -> the stamp (``t_settled`` or
    ``t_ordered``) of the epoch that settled the transaction, over
    EVERY transaction due in the window (in [since, until) where
    given); one that was refused or never settled is beyond every
    percentile."""
    settled_in, at = run["settled_in"], run[stamps]
    out = []
    for tx, due, ok in zip(run["timed"], run["due"], run["timed_ok"]):
        if (since is not None and due < since) or (
            until is not None and due >= until
        ):
            continue
        epoch = settled_in.get(tx) if ok else None
        if epoch is None or epoch >= len(at):
            out.append(math.inf)
        else:
            out.append((at[epoch] - due) * 1e3)
    out.sort()
    return out


__all__ = ["percentile", "latencies_ms"]
