"""HoneyBadger turn: the self time of ``hb/*`` (on_idle and its drains,
start_epoch) and of the protocol spans they cause (``coin/*``,
``settle/*``, ``rbc/*``), share of the traced window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "hb/", "coin/", "settle/", "rbc/")
