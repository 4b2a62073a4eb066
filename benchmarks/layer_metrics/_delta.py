"""Shared by the counter readers: a counter's growth over the window
(drain included, as the epochs' count is), per settled epoch."""


def per_epoch(run, block, key):
    before, after = run["counters"]["before"], run["counters"]["after"]
    if block not in after or block not in before:
        return None
    epochs = after["epochs"] - before["epochs"]
    if epochs <= 0:
        return None
    return (after[block][key] - before[block][key]) / epochs


def compiles(run):
    c = run["counters"]
    return float(c["after"]["compiles"] - c["before"]["compiles"])


def placement_delta(before, after):
    out = {}
    for family, row in after.items():
        base = before.get(family, {})
        out[family] = {k: v - base.get(k, 0) for k, v in row.items()}
    return out


def device_item_pct(run, *prefixes):
    """Of the items the 'tpu' backend's entry points saw in the window
    in the tally's families that start with one of ``prefixes``, the
    share the floors sent to the device; None where they saw none."""
    c = run["counters"]
    delta = placement_delta(c["before"]["placement"], c["after"]["placement"])
    rows = [r for fam, r in delta.items() if fam.startswith(prefixes)]
    device = sum(r.get("device_items", 0) for r in rows)
    host = sum(r.get("host_items", 0) for r in rows)
    if device + host == 0:
        return None
    return 100.0 * device / (device + host)
