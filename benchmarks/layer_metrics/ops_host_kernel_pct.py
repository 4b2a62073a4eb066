"""ops/ seam: the native or Python host kernels that ran the batches
the floors kept off the device (``ops/host``), share of the traced
window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "total_s", "ops/host")
