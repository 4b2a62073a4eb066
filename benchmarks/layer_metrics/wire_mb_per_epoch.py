"""Codec + MAC: megabytes (10^6 bytes) of payload bodies the delivery
plane really decoded (``net.delivery_stats()["bytes_decoded"]``, memo
hits left out) per settled epoch; None where the program does not count
them."""

from benchmarks.layer_metrics._delta import per_epoch


def read(run):
    after = run["counters"]["after"].get("delivery", {})
    before = run["counters"]["before"].get("delivery", {})
    if "bytes_decoded" not in after or "bytes_decoded" not in before:
        return None
    value = per_epoch(run, "delivery", "bytes_decoded")
    return None if value is None else value / 1e6
