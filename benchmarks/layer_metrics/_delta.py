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
