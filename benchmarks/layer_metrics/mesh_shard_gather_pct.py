"""mesh seam: what spreading the waves over the chips and collecting
them costs the host, as a share of the traced window: the total of the
program's ``ops/shard`` spans (host array -> ``NamedSharding``
placement: ``CryptoMesh.put_flat`` / ``put_vl`` / ``put_v``) and
``ops/gather`` spans (a sharded result back as one host array, after
the program has run to its end).  The seam has no device-side
collective, so this is the whole of what the mesh adds to a wave.  A
program without these spans (before PR 29), or a run that never
sharded a batch, reads nothing and the metric is left out."""

from benchmarks.layer_metrics._spans import totals

SPANS = ("ops/shard", "ops/gather")


def read(run):
    table = totals(run)
    window = (run.get("trace") or {}).get("window_s")
    if table is None or not window:
        return None
    rows = [table[name] for name in SPANS if name in table]
    if not rows:
        return None
    return 100.0 * sum(row["total_s"] for row in rows) / window
