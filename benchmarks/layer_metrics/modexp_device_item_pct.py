"""ops/ seam: of the exponentiation items (``modexp_*``: pow, dual_pow,
comb) the 'tpu' backend saw in the window, the share the floors sent to
the device."""

from benchmarks.layer_metrics._delta import device_item_pct


def read(run):
    return device_item_pct(run, "modexp_")
