"""Epoch flight recorder: the structured-tracing half of observability.

The cost model of this stack is dispatch count per epoch, not FLOPs
(docs/ARCHITECTURE.md), yet until this module the only instruments
were coarse counters (`utils/metrics.py`): an N=64 epoch read as one
~12 s number with no way to say whether RBC echo waves, BBA coin
rounds, TPKE verify+combine, or hub flush scheduling bounded the
commit.  The recorder is a per-node bounded ring buffer of typed
events; `tools/tracetool.py` merges N node buffers into one
Chrome-trace-event artifact (Perfetto-loadable) and derives the
per-epoch critical-path report (docs/TRACING.md).

Scoped spans (per batch, per wave, per turn, per phase; never per
message or per item) enter through ONE function, ``span(cat, name,
recorder=None, **args)``.  It is on when a JAX profiler session runs
(observed, ``jax.profiler.TraceAnnotation.is_enabled()``; nobody sets
it) or when the caller hands it a recorder.  On, it enters a
``TraceAnnotation`` named ``cat/name``, so the span lies on the host
plane of the same xplane as the device's "XLA Modules" line: the
profiler's clock is the one clock program and device share.  A
per-thread stack gives every span its parent and its SELF time
(duration less what its children cover); while a session runs those
add up in the process-wide ``totals()``, which the benchmark's
per-layer readers divide by the traced window.  Off, it returns one
shared no-op: no clock read, no allocation, no annotation.

Design constraints, in order:

1. **Compiled-out when off.**  `Config.trace=False` (the default)
   means NO recorder exists: instant sites hold `None` and guard with
   one attribute load + identity check, span sites cost one
   ``is_enabled()`` — no allocation, no clock
   (`tests/test_trace.py`, `tests/test_trace_spans.py`).
2. **Determinism-plane safe.**  Ordering comes from per-node
   **sequence numbers** assigned at record time; `perf_counter`
   timestamps ride along as PURE OBSERVABILITY data that no protocol
   state ever reads back.  This file is the single sanctioned home of
   that clock (the `allow[DET001]` pragmas below); protocol/transport
   code calls `recorder.now()` and never touches `time` itself.  Two
   `PYTHONHASHSEED` runs of one seeded cluster must produce identical
   event sequences — only the timestamps may differ.
3. **Bounded.**  The ring keeps the NEWEST `cap` events and counts
   drops (`stats()`), so an unbounded run can never leak memory into
   the protocol plane.

Event tuple shape (storage; `to_chrome` renders the JSON form):

    (seq, ts, dur, cat, name, args)

    seq   deterministic per-node sequence number (ordering truth)
    ts    perf_counter seconds at record time (observability only)
    dur   None for instant events; span length in seconds otherwise
    cat   one of CATEGORIES
    name  short event name, e.g. "open", "flush", "reveal"
    args  dict of JSON-scalar details (counts, epochs, proposers) —
          MUST be deterministic: no timestamps, no id()s, no set order
"""

from __future__ import annotations

import collections
import gc
import sys
import threading
import time
from typing import Deque, Dict, Iterable, List, Optional, Tuple

from cleisthenes_tpu.utils.determinism import guarded_by
from cleisthenes_tpu.utils.lockcheck import new_lock

# The stage vocabulary: every event belongs to exactly one plane, and
# the critical-path report attributes epoch wall time to these names.
CATEGORIES = frozenset(
    (
        "epoch",  # epoch open / ACS output / commit markers
        "rbc",  # reliable broadcast: VAL/ECHO/READY/deliver
        "bba",  # binary agreement rounds and decisions
        "coin",  # threshold-coin share issue + reveal
        "tpke",  # threshold encryption: encrypt/share/combine
        "settle",  # the trailing decrypt frontier (two-frontier commit
        # split): dec-share issue/combine run by the settler, plus the
        # per-epoch ordered->settled decrypt_lag bracket — kept apart
        # from "tpke" so open->ordered critical paths show exactly the
        # mass that LEFT them
        "hub",  # CryptoHub batched-dispatch flushes
        "router",  # wave-routed ingest demux (protocol.router): one
        # "route" span per delivery wave, args carry frame/payload/
        # dispatch counts — the handler-dispatch amortization record
        "transport",  # envelope coalescing, waves, queue depth
        "ledger",  # WAL appends / checkpoints
        "catchup",  # state-transfer requests/serves/adopts
        "alert",  # SLO watchdog firings (epoch stall, backpressure…)
        "reconfig",  # dynamic membership: one "ceremony" span per
        # reshare (discovery -> qualified set -> finalize) plus
        # discovered/deal/staged/install/activate/teardown instants
        # — the roster-switch timeline tools/tracetool.py reports
        "ingress",  # client admission pipeline (transport/ingress +
        # core/mempool): submit spans per ingress frame, admit/evict
        # instants with the verdict, and one "stream" span per
        # subscriber batch delivery — the client-visible latency
        # timeline the ingress_load bench section measures against
        "ops",  # the ops/ seam: one "ops/<placement family>" span per
        # batched call, children pack / device / unpack (or host when
        # the floor keeps the batch off the device)
        "lockstep",  # protocol.spmd: epoch > propose, rbc_*, bba >
        # coin_wave, decrypt, commit
        "hb",  # one HoneyBadger turn: on_idle > coin_drain, settler,
        # pipeline, deferred, dec_drain; start_epoch
        "gc",  # Python's cyclic collector: one gen0 / gen1 / gen2 span
        # a collection, under the span whose allocation set it off
    )
)

DEFAULT_CAP = 1 << 16

Event = Tuple[int, float, Optional[float], str, str, dict]


@guarded_by("_lock", "_events", "_seq", "_dropped", "_high_water")
class TraceRecorder:
    """One node's flight recorder: a bounded ring of typed events.

    Thread-safe (the gRPC transport records from its dispatcher thread
    while `Metrics.snapshot()` reads stats from callers), but sequence
    numbers are only *meaningful* ordering when the owner records from
    one thread — exactly the single-threaded-actor discipline the
    protocol plane already has.
    """

    def __init__(self, node_id: str, cap: int = DEFAULT_CAP) -> None:
        if cap <= 0:
            raise ValueError(f"trace ring cap {cap} must be > 0")
        self.node_id = node_id
        self.cap = cap
        self._events: Deque[Event] = collections.deque(maxlen=cap)
        self._seq = 0
        self._dropped = 0
        self._high_water = 0
        self._lock = new_lock()

    @staticmethod
    def now() -> float:
        """The observability clock.  Pure data: nothing in the
        protocol plane may branch on this value."""
        return time.perf_counter()  # pure observability (outside the plane)

    # -- recording ---------------------------------------------------------

    def _record(
        self, cat: str, name: str, ts: float, dur: Optional[float], args: dict
    ) -> None:
        with self._lock:
            self._seq += 1
            ring = self._events
            if len(ring) >= self.cap:  # deque(maxlen) evicts the OLDEST
                self._dropped += 1
            ring.append((self._seq, ts, dur, cat, name, args))
            if len(ring) > self._high_water:
                self._high_water = len(ring)

    def instant(self, cat: str, name: str, **args) -> None:
        """A zero-duration marker (quorum crossing, commit, adopt)."""
        self._record(cat, name, self.now(), None, args)

    def complete(self, cat: str, name: str, t0: float, **args) -> None:
        """A span recorded at its END: ``t0`` came from ``now()``
        before the work (the begin/end pair in one call — no nesting
        bookkeeping on the hot path)."""
        t1 = self.now()
        self._record(cat, name, t0, t1 - t0, args)

    # -- reading -----------------------------------------------------------

    def events(self) -> List[Event]:
        """Snapshot of the ring, oldest first."""
        with self._lock:
            return list(self._events)

    def stats(self) -> Dict[str, int]:
        """The Metrics.snapshot()["trace"] block: lifetime recorded
        count, ring-overflow drops, and the buffer high-water mark."""
        with self._lock:
            return {
                "events_recorded": self._seq,
                "events_dropped": self._dropped,
                "high_water": self._high_water,
            }


def maybe_recorder(config, node_id: str) -> Optional[TraceRecorder]:
    """The one construction seam: a recorder iff ``config.trace``,
    else None — and None IS the compiled-out fast path (sites guard
    with ``if tr is not None``)."""
    if getattr(config, "trace", False):
        return TraceRecorder(
            node_id, getattr(config, "trace_buffer", DEFAULT_CAP)
        )
    return None


# ---------------------------------------------------------------------------
# The span entry point: scoped spans on the profiler's clock
# ---------------------------------------------------------------------------

_clock = time.perf_counter  # durations only; the annotation is the timeline
_Annotation = None  # jax.profiler.TraceAnnotation, bound with _session_on


def _session_unbound() -> bool:
    """``_session_on`` until ``jax.profiler`` is loaded: no session
    can run before that, and this module never imports JAX itself."""
    global _session_on, _Annotation
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return False
    _Annotation = profiler.TraceAnnotation
    _session_on = _Annotation.is_enabled
    return _session_on()


_session_on = _session_unbound  # is a JAX profiler session running?

_local = threading.local()  # .stack: this thread's open spans
_totals_lock = threading.Lock()
_totals: Dict[str, List[float]] = {}  # "cat/name" -> [calls, total_s, self_s]
# The collector's rows, a generation each, kept apart from _totals and its
# lock: a collection can start inside that lock, on the thread holding it
# (the row list a new key allocates).  Each row is one tuple, replaced
# whole, so a reader sees it before or after a collection, never between.
_GC_KEYS = ("gc/gen0", "gc/gen1", "gc/gen2")
_GC_EMPTY = ((0, 0.0, 0.0),) * len(_GC_KEYS)
_gc_rows: List[Tuple[int, float, float]] = list(_GC_EMPTY)
_gc_open: Optional["_Collection"] = None  # the collection in progress
_gc_hooked = False  # _on_collection is in gc.callbacks
_hook_lock = threading.Lock()


class _Off:
    """What ``span`` returns when nothing listens: one shared object."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **args) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = (
        "_cat", "_name", "_recorder", "_args", "_key", "_ann", "_late",
        "_t0", "_children_s",
    )

    def __init__(self, cat, name, recorder, args, session) -> None:
        self._cat = cat
        self._name = name
        self._recorder = recorder
        self._args = args
        if session:
            self._key = f"{cat}/{name}"
            self._ann = _Annotation(self._key, **args)
            self._late = None
            self._children_s = 0.0
        else:
            self._ann = None

    def note(self, **args) -> None:
        """Details known only once the work is done (counts, deltas)."""
        self._args.update(args)
        if self._ann is not None:
            self._late = args if self._late is None else {
                **self._late, **args
            }

    # A span is on its thread's stack exactly from its first clock read
    # to its last: whatever allocates outside that stretch (the
    # annotation, the row) can set off a collection, which must then
    # count as the parent's child, inside the parent's wall.

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
            stack = getattr(_local, "stack", None)
            if stack is None:
                stack = _local.stack = []
            stack.append(self)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        dur = _clock() - self._t0
        ann = self._ann
        if ann is not None:
            stack = _local.stack
            if stack[-1] is self:
                stack.pop()
            else:  # not closed innermost-first (a generator's span)
                stack.remove(self)
            if stack:
                stack[-1]._children_s += dur
            if self._late is not None:
                ann.set_metadata(**self._late)
            ann.__exit__(*exc)
            if _session_on():  # a span the session's end cut is left out
                self._tally(dur)
        if self._recorder is not None:
            self._recorder._record(
                self._cat, self._name, self._t0, dur, self._args
            )
        return False

    def _tally(self, dur: float) -> None:
        with _totals_lock:
            row = _totals.get(self._key)
            if row is None:
                row = _totals[self._key] = [0, 0.0, 0.0]
            row[0] += 1
            row[1] += dur
            row[2] += dur - self._children_s


class _Collection(_Span):
    """One run of the cyclic collector: a ``gc/gen<n>`` span on the
    collecting thread's stack, so its time leaves the self time of the
    span it interrupted.  Its row lives in ``_gc_rows``, outside
    ``_totals_lock``."""

    __slots__ = ("_gen",)

    def __init__(self, generation: int) -> None:
        super().__init__("gc", f"gen{generation}", None, {}, session=True)
        self._gen = generation

    def _tally(self, dur: float) -> None:
        calls, total, own = _gc_rows[self._gen]
        _gc_rows[self._gen] = (
            calls + 1, total + dur, own + dur - self._children_s
        )


def _on_collection(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry: while a session runs, a collection is
    a span from its "start" to its "stop"; the first collection that
    finds no session takes the hook out.  The collector does not run
    again until this returns, so one collection is open at a time."""
    global _gc_open
    if phase == "start":
        if not _session_on():
            _unhook_collector()
            return
        _gc_open = _Collection(info["generation"])
        _gc_open.__enter__()
    elif _gc_open is not None:
        sp, _gc_open = _gc_open, None
        sp.note(collected=info["collected"],
                uncollectable=info["uncollectable"])
        sp.__exit__(None, None, None)


def _hook_collector() -> None:
    """Puts the collector on the timeline; ``span`` calls this on its
    session branch only, so a run without a session never hooks it."""
    global _gc_hooked
    with _hook_lock:
        if not _gc_hooked:
            gc.callbacks.append(_on_collection)
            _gc_hooked = True


def _unhook_collector() -> None:
    global _gc_hooked
    _gc_hooked = False
    try:
        gc.callbacks.remove(_on_collection)
    except ValueError:  # another thread's collection took it out first
        pass


def span(cat: str, name: str, recorder: Optional[TraceRecorder] = None, **args):
    """``with span(cat, name, recorder=self.trace, **args) as sp:`` — a
    scoped span at a layer boundary.  On (a profiler session runs, or
    ``recorder`` is given) it is a TraceAnnotation ``cat/name`` on the
    profiler's timeline, a row of ``totals()`` and, with a recorder,
    the ring tuple ``complete()`` appends; ``sp.note(**args)`` adds
    what is known only at the end.  Off it is one shared no-op.  The
    first span of a session also puts the collector on the timeline."""
    session = _session_on()
    if recorder is None and not session:
        return _OFF
    if session and not _gc_hooked:
        _hook_collector()
    return _Span(cat, name, recorder, args, session)


def totals() -> Dict[str, Dict[str, float]]:
    """{"cat/name": {"calls", "total_s", "self_s"}} of every span that
    began and ended inside a profiler session since ``reset_totals()``,
    the collector's ``gc/gen<n>`` among them once one has run.  Self
    times of one thread's spans partition that thread's wall."""
    rows = {key: row for key, row in zip(_GC_KEYS, _gc_rows) if row[0]}
    with _totals_lock:
        rows.update((key, tuple(row)) for key, row in _totals.items())
    return {
        key: {"calls": row[0], "total_s": row[1], "self_s": row[2]}
        for key, row in sorted(rows.items())
    }


def reset_totals() -> None:
    with _totals_lock:
        _totals.clear()
    _gc_rows[:] = _GC_EMPTY


# ---------------------------------------------------------------------------
# Chrome-trace-event rendering (the Perfetto-loadable artifact)
# ---------------------------------------------------------------------------


def to_chrome(events_by_node: Dict[str, Iterable[Event]]) -> dict:
    """Merge N node buffers into one Chrome trace-event document:
    one track (tid) per node, instants as 'i' events, spans as 'X'
    complete events (self-nesting in the viewer), timestamps
    normalized to the earliest event and scaled to microseconds.

    The per-node ``seq`` rides in ``args.seq`` — it is the ordering
    ground truth (`tools/tracetool.py --validate` checks it is
    strictly increasing per track; timestamps are allowed to be
    whatever the clock said).
    """
    nodes = sorted(events_by_node)
    all_events = {n: list(events_by_node[n]) for n in nodes}
    t_min = min(
        (ev[1] for evs in all_events.values() for ev in evs),
        default=0.0,
    )
    trace_events: List[dict] = []
    for tid, node in enumerate(nodes, start=1):
        trace_events.append(
            {
                "ph": "M",
                "pid": 1,
                "tid": tid,
                "name": "thread_name",
                "args": {"name": node},
            }
        )
        for seq, ts, dur, cat, name, args in all_events[node]:
            ev = {
                "pid": 1,
                "tid": tid,
                "cat": cat,
                "name": name,
                "ts": round((ts - t_min) * 1e6, 3),
                "args": {"seq": seq, **args},
            }
            if dur is None:
                ev["ph"] = "i"
                ev["s"] = "t"  # thread-scoped instant
            else:
                ev["ph"] = "X"
                ev["dur"] = round(dur * 1e6, 3)
            trace_events.append(ev)
    return {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "metadata": {
            "tool": "cleisthenes_tpu.utils.trace",
            "nodes": nodes,
        },
    }


def write_chrome(path: str, events_by_node: Dict[str, Iterable[Event]]) -> None:
    """Serialize ``to_chrome`` to ``path`` (open the file in Perfetto
    via ui.perfetto.dev -> Open trace file; see docs/TRACING.md)."""
    import json

    with open(path, "w", encoding="utf-8") as fh:
        json.dump(to_chrome(events_by_node), fh)


__all__ = [
    "CATEGORIES",
    "DEFAULT_CAP",
    "TraceRecorder",
    "maybe_recorder",
    "reset_totals",
    "span",
    "to_chrome",
    "totals",
    "write_chrome",
]
