"""ops/ seam: Python ints and bytes into numpy columns and back
(self time of ``ops/pack`` and ``ops/unpack``), share of the traced
window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "ops/pack", "ops/unpack")
