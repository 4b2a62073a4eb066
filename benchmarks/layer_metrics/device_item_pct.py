"""ops/ seam: of all items the 'tpu' backend's entry points saw in the
window, the share the floors sent to the device (``ops/placement``)."""

from benchmarks.layer_metrics._delta import device_item_pct


def read(run):
    return device_item_pct(run, "")
