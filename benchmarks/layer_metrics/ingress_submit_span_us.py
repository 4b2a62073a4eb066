"""Ingress + mempool: the program's own span around one admission
(``ingress/submit``), mean over the traced part's submits.  It lies
inside the harness's span that ``ingress_submit_us_p50`` reads."""

from benchmarks.layer_metrics._spans import totals


def read(run):
    row = (totals(run) or {}).get("ingress/submit")
    if not row or not row["calls"]:
        return None
    return 1e6 * row["total_s"] / row["calls"]
