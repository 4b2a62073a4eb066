"""All layers: the self time of every program span, share of the traced
window: what the spans see; 100 less it is what they do not."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "")
