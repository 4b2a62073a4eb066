"""ops/ seam: of the Merkle items (``merkle.*``: leaves built, branches
verified) the 'tpu' backend saw in the window, the share the floors
sent to the device."""

from benchmarks.layer_metrics._delta import device_item_pct


def read(run):
    return device_item_pct(run, "merkle.")
