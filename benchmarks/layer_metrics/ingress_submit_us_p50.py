"""Ingress + mempool: median of the harness's span around one
``IngressPlane`` submit (frame encode, admission, ack decode)."""

import statistics


def read(run):
    spans = run.get("submit_s")
    if not spans:
        return None
    return statistics.median(spans) * 1e6
