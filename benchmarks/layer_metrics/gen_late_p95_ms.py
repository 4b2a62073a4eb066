"""Load generator: how late the harness submitted, 95th percentile of
submitted - due over every transaction due in the window.  A starved
generator must not be read as a fast server."""

import math


def read(run):
    late = sorted(run.get("late_s") or ())
    if not late:
        return None
    return late[max(0, math.ceil(0.95 * len(late)) - 1)] * 1e3
