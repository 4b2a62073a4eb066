"""Shared by the fault-schedule readers: the harness's own record of
what the schedule did (``Served.fault_report``: the events as applied,
each validator's outage, the delivery waves of every round), and the
two moments the phases of a run are cut at.  All on the harness's own
stamps and counts, over the whole window."""


def report(run):
    return run.get("faults") or None


def first_event(run, kind):
    """The first event of ``kind`` (``kill`` or ``restart``) as it was
    applied, or None."""
    for ev in (report(run) or {}).get("events", ()):
        if ev["kind"] == kind:
            return ev
    return None


def degraded(run):
    """(t_kill, t_restart): the stretch with validators down; it ends
    with the window's drain where the schedule restarts nobody.  None
    where nothing was killed."""
    kill = first_event(run, "kill")
    if kill is None:
        return None
    restart = first_event(run, "restart")
    at_rest = run["t_settled"][-1] if run.get("t_settled") else run["t_end"]
    return kill["t"], (restart["t"] if restart else max(run["t_end"], at_rest))


def settle_pctl_ms(run, q, since=None, until=None):
    """``settle_p<q>_ms`` as run.py reads it (benchmarks/latency.py),
    of the transactions due in [since, until) where given; None of an
    empty sample, or where that share of it never settled."""
    import math

    from benchmarks.latency import latencies_ms, percentile

    value = percentile(latencies_ms(run, "t_settled", since, until), q)
    return value if math.isfinite(value) else None
