"""mesh seam: of the items the floors sent to the device in the window
(``ops/placement``, all families), the share whose batch ran sharded
over more than one device (``mesh_items``).  0 means the mesh was not
in effect, whatever ``device.count`` says.  A program whose tally has
no such column (before PR 29) reads nothing and the metric is left
out, as does a window in which the device saw no item."""

from benchmarks.layer_metrics._delta import placement_delta


def read(run):
    c = run["counters"]
    rows = placement_delta(
        c["before"]["placement"], c["after"]["placement"]
    ).values()
    if not any("mesh_items" in row for row in rows):
        return None
    device = sum(row.get("device_items", 0) for row in rows)
    if device == 0:
        return None
    return 100.0 * sum(row.get("mesh_items", 0) for row in rows) / device
