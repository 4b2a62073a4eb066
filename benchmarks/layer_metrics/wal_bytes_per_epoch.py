"""WAL: bytes all the validators' logs grew by over the window (drain
included, as the epochs' count is), per settled epoch: the harness's
own look at the files' sizes (``counters()["wal_bytes"]``)."""


def read(run):
    before, after = run["counters"]["before"], run["counters"]["after"]
    if "wal_bytes" not in after or "wal_bytes" not in before:
        return None
    epochs = after["epochs"] - before["epochs"]
    if epochs <= 0:
        return None
    return (after["wal_bytes"] - before["wal_bytes"]) / epochs
