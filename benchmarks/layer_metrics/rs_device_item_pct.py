"""ops/ seam: of the Reed-Solomon items (``rs_gf*``: encodes, decodes,
rechecks) the 'tpu' backend saw in the window, the share the floors
sent to the device."""

from benchmarks.layer_metrics._delta import device_item_pct


def read(run):
    return device_item_pct(run, "rs_gf")
