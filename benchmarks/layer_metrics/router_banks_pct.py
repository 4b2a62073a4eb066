"""WaveRouter and the RBC/BBA banks: the self time of ``router/*``
(the demux and the bank handlers it calls), share of the traced
window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "router/")
