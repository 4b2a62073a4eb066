"""Codec + MAC: payload bodies the delivery plane really decoded
(``net.delivery_stats()["frames_decoded"]``, memo hits left out) per
settled epoch."""

from benchmarks.layer_metrics._delta import per_epoch


def read(run):
    return per_epoch(run, "delivery", "frames_decoded")
