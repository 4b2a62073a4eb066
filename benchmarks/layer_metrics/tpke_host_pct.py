"""tpke batch layer: its own Python (nonce draw, regrouping, CP
challenges, share objects), that is the self time of ``tpke/*`` with
the ops/ calls beneath taken out, share of the traced window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "tpke/")
