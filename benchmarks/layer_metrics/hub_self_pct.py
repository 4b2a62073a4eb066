"""CryptoHub: the self time of ``hub/*`` (flush, drain, and the three
executors less the ops/ and tpke calls beneath), share of the traced
window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "hub/")
