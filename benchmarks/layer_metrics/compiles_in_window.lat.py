"""XLA compile: compilations (jax.monitoring) between the window's
start and the end of the drain.  Should read 0."""

from benchmarks.layer_metrics._delta import compiles


def read(run):
    return compiles(run)
