"""Measurement mutual exclusion + host-load provenance.

A second python process sharing the host's cores with a capture
inflated every CPU section ~2x once (protocol_n16 994 ms vs 462 ms
on a quiet box), and the artifact could not prove the contamination
because its provenance recorded nothing about host contention.  This
module fixes both halves:

1. MUTUAL EXCLUSION — one flock'd lockfile shared by every measuring
   driver (bench.py, tools/ab_live.py, tools/profile_*.py).  While a
   holder measures, no other driver measures.
2. PAUSABLE LOW-PRIORITY JOBS — hours-long background work
   (tools/sweep_roster.py) registers its pid; acquiring the lock
   SIGSTOPs registered jobs for the duration and SIGCONTs them on
   release, so a measurement can start without the sweep
   contaminating the timing (and without losing the sweep's progress).
   A detached guardian subprocess resumes the jobs even if the holder
   is SIGKILLed mid-capture.
3. LOAD PROVENANCE — load_snapshot() records os.getloadavg() and the
   competing-python-process count so the next contaminated artifact is
   self-incriminating instead of silently wrong.

Reentrancy: a holder exports CLEISTHENES_BENCH_LOCK=<pid> so child
processes it spawns (bench.py --ab -> tools/abench.py samples) see
the lock as already held and no-op instead of deadlocking on the
flock.
"""

from __future__ import annotations

import contextlib
import fcntl
import os
import signal
import subprocess
import sys
import time

LOCK_PATH = "/tmp/cleisthenes_bench.lock"
PAUSE_DIR = "/tmp/cleisthenes_pausable"
_ENV_KEY = "CLEISTHENES_BENCH_LOCK"


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


def _pausable_pids() -> list[int]:
    if not os.path.isdir(PAUSE_DIR):
        return []
    pids = []
    for name in os.listdir(PAUSE_DIR):
        try:
            pid = int(name)
        except ValueError:
            continue
        if _alive(pid):
            pids.append(pid)
        else:  # stale registration from a dead job
            with contextlib.suppress(OSError):
                os.unlink(os.path.join(PAUSE_DIR, name))
    return pids


def _lock_is_held() -> bool:
    """True when some live holder currently flocks LOCK_PATH."""
    try:
        fd = os.open(LOCK_PATH, os.O_CREAT | os.O_RDWR, 0o666)
    except OSError:
        return False
    try:
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except BlockingIOError:
            return True
        fcntl.flock(fd, fcntl.LOCK_UN)
        return False
    finally:
        os.close(fd)


def register_pausable() -> None:
    """Called by hours-long background jobs (the adversarial sweep):
    lock holders SIGSTOP me while they measure, SIGCONT me after.

    If a capture is ALREADY in flight when we register, stop ourselves
    now: the holder snapshotted the pause set at acquire time and
    cannot see us, but release re-scans the registry and CONTs every
    registered job, so we wake exactly when the capture ends."""
    os.makedirs(PAUSE_DIR, exist_ok=True)
    path = os.path.join(PAUSE_DIR, str(os.getpid()))
    with open(path, "w") as f:
        f.write(sys.argv[0] if sys.argv else "?")
    import atexit

    def _cleanup() -> None:
        with contextlib.suppress(OSError):
            os.unlink(path)

    atexit.register(_cleanup)
    while _lock_is_held():  # loop: a spurious wake re-checks
        os.kill(os.getpid(), signal.SIGSTOP)


def _spawn_guardian(paused: list[int]) -> "subprocess.Popen | None":
    """Detached watchdog: if the lock holder dies without releasing
    (SIGKILL by the driver's timeout is realistic), SIGCONT the paused
    jobs so a frozen sweep never outlives the capture that froze it.

    The resume condition is the FLOCK becoming free, not holder-pid
    liveness: a successor holder that acquired within the poll window
    keeps the lock busy, so the guardian never CONTs jobs the
    successor just paused, and pid reuse cannot fool it."""
    if not paused:
        return None
    code = (
        "import os,sys,time,fcntl,signal\n"
        "lock=sys.argv[1]; pids=[int(p) for p in sys.argv[2:]]\n"
        "while True:\n"
        "    time.sleep(5)\n"
        "    try:\n"
        "        fd=os.open(lock,os.O_CREAT|os.O_RDWR,0o666)\n"
        "    except OSError:\n"
        "        continue\n"
        "    try:\n"
        "        try: fcntl.flock(fd,fcntl.LOCK_EX|fcntl.LOCK_NB)\n"
        "        except BlockingIOError:\n"
        "            continue\n"
        "        for p in pids:\n"
        "            try: os.kill(p,signal.SIGCONT)\n"
        "            except OSError: pass\n"
        "        break\n"
        "    finally:\n"
        "        os.close(fd)\n"
    )
    try:
        return subprocess.Popen(
            [sys.executable, "-c", code, LOCK_PATH]
            + [str(p) for p in paused],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            start_new_session=True,
        )
    except OSError:
        return None


@contextlib.contextmanager
def hold(name: str, block: bool = True, timeout_s: float = 7200.0):
    """Exclusive measurement lock.  Yields True when held (or already
    held by an ancestor — reentrant via env), False when block=False
    and the lock is busy.  Pauses registered low-priority jobs."""
    if os.environ.get(_ENV_KEY):  # ancestor holds it: reentrant no-op
        yield True
        return
    fd = os.open(LOCK_PATH, os.O_CREAT | os.O_RDWR, 0o666)
    try:
        if block:
            deadline = time.time() + timeout_s
            while True:
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    break
                except BlockingIOError:
                    if time.time() >= deadline:
                        raise TimeoutError(
                            f"bench lock busy for {timeout_s}s "
                            f"(holder: {_read_holder()})"
                        )
                    time.sleep(2)
        else:
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except BlockingIOError:
                # the outer finally closes fd — closing here too
                # makes every busy non-blocking probe die with EBADF
                # on exit
                yield False
                return
        os.ftruncate(fd, 0)
        os.write(fd, f"{os.getpid()} {name} {time.time():.0f}".encode())
        os.environ[_ENV_KEY] = str(os.getpid())
        paused = _pausable_pids()
        guardian = _spawn_guardian(paused)
        for pid in paused:
            with contextlib.suppress(OSError):
                os.kill(pid, signal.SIGSTOP)
        try:
            yield True
        finally:
            # re-scan: jobs that registered DURING the capture stopped
            # themselves (register_pausable) and wait on this CONT
            for pid in set(paused) | set(_pausable_pids()):
                with contextlib.suppress(OSError):
                    os.kill(pid, signal.SIGCONT)
            if guardian is not None:
                with contextlib.suppress(OSError):
                    guardian.kill()
            os.environ.pop(_ENV_KEY, None)
            fcntl.flock(fd, fcntl.LOCK_UN)
    finally:
        os.close(fd)


def _read_holder() -> str:
    try:
        with open(LOCK_PATH) as f:
            return f.read().strip() or "?"
    except OSError:
        return "?"


def load_snapshot() -> dict:
    """Host-contention evidence for artifact provenance."""
    snap: dict = {"loadavg": [round(x, 2) for x in os.getloadavg()]}
    me = os.getpid()
    competing = []
    try:
        for entry in os.listdir("/proc"):
            if not entry.isdigit() or int(entry) == me:
                continue
            try:
                with open(f"/proc/{entry}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\x00", b" ").decode(
                        "utf-8", "replace").strip()
                with open(f"/proc/{entry}/stat") as f:
                    state = f.read().split(")")[-1].split()[0]
            except OSError:
                continue
            # running/runnable python processes are the contamination
            # vector on a one-core box; stopped (T) ones are paused
            if "python" in cmd and state in ("R", "D"):
                competing.append(cmd[:80])
    except OSError:
        pass
    snap["competing_python_procs"] = len(competing)
    if competing:
        snap["competing_cmdlines"] = competing[:6]
    snap["paused_jobs"] = len(_pausable_pids())
    return snap
