"""Served round (every validator's ``start_epoch``, then deliveries and
idle phases to quiescence): the window's length over its rounds, held
backlog only.  Where a window holds three rounds it is the number
``settled_tx_per_s`` is made of."""


def read(run):
    rounds = run.get("rounds_in_window")
    if not rounds:
        return None
    return 1e3 * (run["t_end"] - run["t0"]) / rounds
