"""CryptoHub: batch dispatches (``hub.stats()["dispatches"]``) per
settled epoch."""

from benchmarks.layer_metrics._delta import per_epoch


def read(run):
    return per_epoch(run, "hub", "dispatches")
