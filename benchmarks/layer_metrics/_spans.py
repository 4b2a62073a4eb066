"""Shared by the span readers: the program's own spans
(``cleisthenes_tpu.utils.trace.span``) as totals over the traced part
of the window.  The table fills only while the profiler's session
runs, so it holds that part and nothing else; a share divides by the
traced window's length.  A checkout whose program has no such table,
or a run with no trace, reads None and the metric is left out."""


def totals(run):
    """{"cat/name": {"calls", "total_s", "self_s"}} or None."""
    if not run.get("trace"):
        return None
    try:
        from cleisthenes_tpu.utils import trace
    except ImportError:
        return None
    read = getattr(trace, "totals", None)
    if read is None:
        return None
    return read() or None


def window_pct(run, field, *prefixes):
    """100 x the summed ``field`` (``self_s`` or ``total_s``) of the
    spans whose name starts with one of ``prefixes``, over the traced
    window.  A program with spans but none of these reads 0."""
    table = totals(run)
    window = (run.get("trace") or {}).get("window_s")
    if table is None or not window:
        return None
    seconds = sum(
        row[field] for name, row in table.items()
        if name.startswith(prefixes)
    )
    return 100.0 * seconds / window
