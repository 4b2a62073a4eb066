"""ops/ seam: of all items the 'tpu' backend's entry points saw in the
window, the share the floors sent to the device (``ops/placement``)."""

from benchmarks.layer_metrics._delta import placement_delta


def read(run):
    c = run["counters"]
    delta = placement_delta(c["before"]["placement"], c["after"]["placement"])
    device = sum(r.get("device_items", 0) for r in delta.values())
    host = sum(r.get("host_items", 0) for r in delta.values())
    if device + host == 0:
        return None
    return 100.0 * device / (device + host)
