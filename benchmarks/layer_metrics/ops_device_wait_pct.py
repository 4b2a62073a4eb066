"""ops/ seam: what the host waits for the chip (``ops/device``: from
placing the inputs until ``np.asarray(out)`` has returned, so transfer
in, dispatch, execution, transfer out), share of the traced window.
No less than the device's busy share of the same line."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "total_s", "ops/device")
