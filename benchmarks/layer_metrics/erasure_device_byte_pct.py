"""ops/ seam: of the bytes the erasure-code and hash families
(``rs_gf*``, ``merkle.*``, ``sha256.*``) were handed in the window, the
share the floors sent to the device: the byte twin of
rs_device_item_pct and merkle_device_item_pct.  The Merkle floors count
items, so a wave of a few hundred 43 KB leaves stays on the host however
many bytes it hashes, and this number shows it.  None where the tally
has no byte counts or the families saw nothing."""

from benchmarks.layer_metrics._delta import placement_delta

FAMILIES = ("rs_gf", "merkle.", "sha256.")


def read(run):
    c = run["counters"]
    delta = placement_delta(c["before"]["placement"], c["after"]["placement"])
    rows = [r for fam, r in delta.items() if fam.startswith(FAMILIES)]
    if not rows or any("device_bytes" not in r for r in rows):
        return None
    device = sum(r["device_bytes"] for r in rows)
    host = sum(r["host_bytes"] for r in rows)
    if device + host == 0:
        return None
    return 100.0 * device / (device + host)
