"""Python's cyclic collector on the program's timeline (utils/trace.py).

While a profiler session runs, every collection is a ``gc/gen<n>`` span
on the collecting thread's stack: it nests under the span whose
allocation set it off, leaves that span's self time, and adds a row to
``trace.totals()``.  Without a session nothing is hooked.  The
collector's rows stay out of ``_totals_lock``, which a collection can
interrupt on the thread that holds it."""

from __future__ import annotations

import gc
import pathlib
import sys
import threading

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from cleisthenes_tpu.utils import trace  # noqa: E402
from tests.test_trace_spans import FakeAnnotation, span_tree  # noqa: E402
from tools import tracetool  # noqa: E402


@pytest.fixture
def quiet():
    """No automatic collection: a test's collections are the ones it
    forces.  The hook is taken out again whatever the test left."""
    enabled = gc.isenabled()
    gc.disable()
    trace.reset_totals()
    yield
    trace._unhook_collector()
    trace.reset_totals()
    if enabled:
        gc.enable()


@pytest.fixture
def session(quiet, monkeypatch):
    """A profiler session as the span entry point observes one; unlike
    the span tests' fixture, this one lets the collector's hook in."""
    state = {"on": True}
    FakeAnnotation.log = []
    monkeypatch.setattr(trace, "_session_on", lambda: state["on"])
    monkeypatch.setattr(trace, "_Annotation", FakeAnnotation)
    yield state


def test_without_a_session_nothing_is_hooked_or_counted(quiet, monkeypatch):
    monkeypatch.setattr(trace, "_session_on", lambda: False)
    before = list(gc.callbacks)
    recorder = trace.TraceRecorder("n0")  # Config.trace=True, no session
    with trace.span("router", "route"):
        with trace.span("hub", "flush", recorder=recorder):
            gc.collect()
    assert gc.callbacks == before and not trace._gc_hooked
    assert trace.totals() == {}
    assert [e[3:5] for e in recorder.events()] == [("hub", "flush")]


def test_a_collection_leaves_the_self_time_of_the_span_it_interrupted(
    session,
):
    with trace.span("router", "route"):
        garbage = [[]]
        garbage[0].append(garbage)  # a cycle only the collector frees
        del garbage
        gc.collect()
    got = trace.totals()
    assert set(got) == {"router/route", "gc/gen2"}
    gen2, route = got["gc/gen2"], got["router/route"]
    assert gen2["calls"] == 1 and gen2["self_s"] == gen2["total_s"] > 0
    assert route["self_s"] == pytest.approx(
        route["total_s"] - gen2["total_s"], abs=1e-12
    )
    assert route["self_s"] >= 0
    ((name, _args, children),) = span_tree(FakeAnnotation.log)
    assert name == "router/route"
    ((child, args, grandchildren),) = children
    assert child == "gc/gen2" and grandchildren == []
    assert args["collected"] >= 2 and args["uncollectable"] == 0


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_each_generation_has_its_row_and_none_needs_a_parent(
    session, generation
):
    with trace.span("hub", "flush"):  # the first span hooks the collector
        pass
    gc.collect(generation)  # no span open: a top-level span
    got = trace.totals()
    row = got[f"gc/gen{generation}"]
    assert row["calls"] == 1 and row["self_s"] == row["total_s"]
    assert set(got) == {"hub/flush", f"gc/gen{generation}"}
    assert [n for n, _a, _c in span_tree(FakeAnnotation.log)] == [
        "hub/flush", f"gc/gen{generation}",
    ]
    trace.reset_totals()
    assert trace.totals() == {}


def test_the_hook_leaves_at_the_first_collection_after_the_session(session):
    before = list(gc.callbacks)
    with trace.span("hub", "flush"):
        pass
    assert gc.callbacks == before + [trace._on_collection]
    with trace.span("hub", "flush"):  # hooked once, not once a span
        pass
    assert gc.callbacks.count(trace._on_collection) == 1
    session["on"] = False
    gc.collect()
    assert gc.callbacks == before and not trace._gc_hooked
    assert "gc/gen2" not in trace.totals()
    session["on"] = True  # the next session hooks it again
    with trace.span("hub", "flush"):
        gc.collect()
    assert trace.totals()["gc/gen2"]["calls"] == 1


def test_a_collection_inside_the_totals_lock_finishes(session):
    """``_Span.__exit__`` allocates a new key's row while it holds
    ``_totals_lock``; a collection set off there closes its own span on
    the same thread, which must not wait for that lock."""
    with trace.span("hub", "flush"):
        pass
    errors: list = []

    def work():
        try:
            with trace._totals_lock:
                gc.collect()
            threshold = gc.get_threshold()
            gc.set_threshold(1)  # a collection at every allocation
            gc.enable()
            try:
                for i in range(40):
                    with trace.span("ops", f"fresh{i}"):  # a new row each
                        pass
            finally:
                gc.disable()
                gc.set_threshold(*threshold)
        except Exception as exc:  # reported below, not lost in the thread
            errors.append(exc)

    worker = threading.Thread(target=work, daemon=True)
    worker.start()
    worker.join(30)
    if worker.is_alive():  # let the suite go on past the deadlock, then fail
        trace._totals_lock.release()
        worker.join(10)
        pytest.fail("a collection deadlocked on _totals_lock")
    assert errors == []
    got = trace.totals()
    assert got["gc/gen2"]["calls"] >= 1
    assert got["gc/gen0"]["calls"] >= 40
    assert all(got[f"ops/fresh{i}"]["calls"] == 1 for i in range(40))
    assert all(row["self_s"] >= 0 for row in got.values())


def test_collections_lie_on_the_profilers_timeline(quiet, tmp_path):
    """A real session on the CPU platform: ``gc/gen2`` is an annotation
    inside the span it interrupted, a program span to the operator's
    tool, and the hook is gone after the session's end."""
    import jax.profiler

    from benchmarks import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    before = list(gc.callbacks)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.span("router", "route"):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    got = trace.totals()
    gc.collect()
    assert gc.callbacks == before
    assert got["gc/gen2"]["calls"] == 1
    loaded = trace_reduce.load_xplane(str(tmp_path), set(got))
    events = {
        ev[0]: ev for plane in loaded["planes"]
        if plane["name"].startswith("/host:")
        for line in plane["lines"] for ev in line["events"]
    }
    route, gen2 = events["router/route"], events["gc/gen2"]
    assert route[1] <= gen2[1] and gen2[1] + gen2[2] <= route[1] + route[2]
    assert tracetool.profile_span_names(str(tmp_path)) == set(got)
