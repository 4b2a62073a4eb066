"""Compilation and dispatch meters, copied from chip_smoke.py
(CompileMeter, dispatch_round_trip_us) so that the yardstick lives
where a later PR cannot change it; and SyncMeter, the harness's own
reading of what reached the disk."""

from __future__ import annotations

import functools
import os
import statistics
import time
from typing import Dict, List, Tuple

import numpy as np

# jax.monitoring's name for one jit-cache miss going through
# compile_or_get_cached (jax._src.dispatch.BACKEND_COMPILE_EVENT, 0.9.0)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Counts XLA compilations and the seconds they took, and keeps
    each one's program name (the event's ``fun_name``) in order, so
    that a run which compiles inside its window can say what.  With a
    warm persistent cache a compilation is a cache read: same count,
    far fewer seconds, and ``cache_hits`` says how many were reads."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration
            self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


class SyncMeter:
    """What the last ``os.fsync`` / ``os.fdatasync`` of each file left
    on the disk: between ``install()`` and ``remove()`` the two calls
    are wrapped, and every one that returns is counted and the file's
    ``st_size`` at that moment kept by (``st_dev``, ``st_ino``).  A
    loss of power leaves of a file what it held at its last sync, so
    ``synced_bytes(path)`` is the prefix a ``durable_after: fsync``
    configuration may count on; a file never synced reads 0.  It takes
    nothing from the program."""

    _CALLS = ("fsync", "fdatasync")

    def __init__(self) -> None:
        self.count = 0
        self._sizes: Dict[Tuple[int, int], int] = {}
        self._orig: Dict[str, object] = {}

    def install(self) -> None:
        for name in self._CALLS:
            orig = getattr(os, name)
            self._orig[name] = orig
            setattr(os, name, functools.partial(self._sync, orig))

    def remove(self) -> None:
        for name, orig in self._orig.items():
            setattr(os, name, orig)
        self._orig = {}

    def _sync(self, orig, fd) -> None:
        orig(fd)
        # a file object is as good as a descriptor to os.fsync
        st = os.fstat(fd if isinstance(fd, int) else fd.fileno())
        self.count += 1
        self._sizes[(st.st_dev, st.st_ino)] = st.st_size

    def synced_bytes(self, path: str) -> int:
        st = os.stat(path)
        return self._sizes.get((st.st_dev, st.st_ino), 0)


@functools.cache
def _bump():
    import jax

    def benchmark_probe_bump(a):
        return a + 1

    return jax.jit(benchmark_probe_bump)


def probe_round_trip() -> float:
    """One tiny forced round trip, in seconds: a 4 KiB numpy array to
    the device, one jitted add, the result back as numpy.  It is the
    harness's own proof that the device answers; the served N=16 cells
    send nothing else there (PERF.md section 3)."""
    import jax.numpy as jnp

    x = np.zeros((8, 128), dtype=np.int32)
    t0 = time.perf_counter()
    out = np.asarray(_bump()(jnp.asarray(x)))
    wall = time.perf_counter() - t0
    if int(out[0, 0]) != 1:
        raise RuntimeError("the device returned a wrong probe result")
    return wall


def dispatch_round_trip_us(reps: int = 20) -> float:
    """Median of ``reps`` round trips in us, compiled beforehand."""
    probe_round_trip()
    return statistics.median(probe_round_trip() for _ in range(reps)) * 1e6


__all__ = ["CompileMeter", "SyncMeter", "probe_round_trip",
           "dispatch_round_trip_us"]
