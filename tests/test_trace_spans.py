"""The span entry point (utils/trace.span) and the spans beneath it.

Off: one shared no-op, no clock, no allocation.  On: a TraceAnnotation
on the profiler's own timeline, a per-thread stack that gives each
span its self time, and the process-wide totals the benchmark's
per-layer readers divide by the traced window.  Then the sites: every
placement family of the ops/ seam on both sides of its floor, the
lockstep executor's phases in a real profile on the CPU platform, and
the ring of a seeded traced cluster, which the new spans must leave
as it was."""

from __future__ import annotations

import hashlib
import pathlib
import sys
import threading

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from cleisthenes_tpu.config import Config  # noqa: E402
from cleisthenes_tpu.ops import placement  # noqa: E402
from cleisthenes_tpu.utils import trace  # noqa: E402
from tools import tracetool  # noqa: E402


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs what a
    profiler session would have been given."""

    log: list = []

    def __init__(self, name, **args):
        self.name = name
        self.args = dict(args)

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name, self.args))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name, self.args))
        return False

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture
def session(monkeypatch):
    """A profiler session as the span entry point observes one.  The
    collector stays off the timeline: a collection these tests did not
    force would add a span to the exact logs and clocks below
    (tests/test_trace_gc.py lets it in)."""
    state = {"on": True}
    FakeAnnotation.log = []
    monkeypatch.setattr(trace, "_session_on", lambda: state["on"])
    monkeypatch.setattr(trace, "_Annotation", FakeAnnotation)
    monkeypatch.setattr(trace, "_hook_collector", lambda: None)
    trace.reset_totals()
    placement.reset()
    yield state
    trace.reset_totals()


def span_tree(log):
    """[(name, args, [children])] from the enter/exit log of one thread."""
    roots: list = []
    stack: list = []
    for what, name, args in log:
        if what == "enter":
            node = (name, args, [])
            (stack[-1][2] if stack else roots).append(node)
            stack.append(node)
        else:
            assert stack.pop()[0] == name
    assert not stack
    return roots


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------


def test_off_path_is_one_shared_noop_without_clock_or_allocation(monkeypatch):
    import tracemalloc

    def no_clock():
        raise AssertionError("the off path read a clock")

    monkeypatch.setattr(trace, "_session_on", lambda: False)
    monkeypatch.setattr(trace, "_clock", no_clock)
    first = trace.span("ops", "pack")
    assert first is trace.span("hub", "flush", items=3) and not first
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[1]
        for _ in range(10_000):
            with trace.span("ops", "device", program="x") as sp:
                sp.note(items=1)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak - base < 512
    assert trace.totals() == {}


def test_session_probe_binds_once_jax_profiler_is_loaded():
    import jax.profiler

    assert trace._session_unbound() is False  # no session in the tests
    assert trace._session_on == jax.profiler.TraceAnnotation.is_enabled
    assert trace._Annotation is jax.profiler.TraceAnnotation


def test_self_time_is_duration_less_children_on_each_thread(
    session, monkeypatch
):
    ticks = threading.local()

    def clock():  # every read on a thread is one second after its last
        ticks.t = getattr(ticks, "t", -1.0) + 1.0
        return ticks.t

    monkeypatch.setattr(trace, "_clock", clock)
    gate = threading.Barrier(2, timeout=10)

    def work(children):
        with trace.span("hub", "flush"):  # enters at 0
            for _ in range(children):
                gate.wait()  # the two threads' spans interleave
                with trace.span("hub", "shares"):  # one second each
                    with trace.span("ops", "host"):  # of which one inside
                        pass

    a = threading.Thread(target=work, args=(2,))
    b = threading.Thread(target=lambda: (work(1), gate.wait()))
    a.start(), b.start()
    a.join(10), b.join(10)
    assert not a.is_alive() and not b.is_alive()
    # a thread's reads: flush 0, then per child 1..4 (enter shares,
    # enter host, exit host, exit shares), then flush's exit
    got = trace.totals()
    assert got["hub/flush"] == {
        "calls": 2, "total_s": 9.0 + 5.0, "self_s": 3.0 + 2.0,
    }
    assert got["hub/shares"] == {"calls": 3, "total_s": 9.0, "self_s": 6.0}
    assert got["ops/host"] == {"calls": 3, "total_s": 3.0, "self_s": 3.0}
    # self times partition each thread's wall
    assert sum(r["self_s"] for r in got.values()) == 9.0 + 5.0


def test_totals_fill_only_inside_a_session_and_reset(session):
    tr = trace.TraceRecorder("n0")
    session["on"] = False
    with trace.span("rbc", "propose", recorder=tr, epoch=1):
        pass
    assert trace.totals() == {} and FakeAnnotation.log == []
    assert [(e[3], e[4], e[5]) for e in tr.events()] == [
        ("rbc", "propose", {"epoch": 1})
    ]
    session["on"] = True
    with trace.span("rbc", "propose", recorder=tr, epoch=2) as sp:
        sp.note(bytes=7)
    assert trace.totals()["rbc/propose"]["calls"] == 1
    assert tr.events()[-1][5] == {"epoch": 2, "bytes": 7}
    # the late args reach the annotation too
    assert FakeAnnotation.log[-1] == (
        "exit", "rbc/propose", {"epoch": 2, "bytes": 7}
    )
    # a span that the session's end cut short is left out
    with trace.span("hub", "flush"):
        session["on"] = False
    assert "hub/flush" not in trace.totals()
    trace.reset_totals()
    assert trace.totals() == {}


def test_new_categories_are_known_to_the_validator():
    assert {"ops", "lockstep", "hb", "gc"} <= trace.CATEGORIES
    assert not hasattr(trace.TraceRecorder, "span")


# ---------------------------------------------------------------------------
# the ops/ seam: every placement family, both sides of its floor
# ---------------------------------------------------------------------------

RNG = np.random.default_rng(5)


def _modexp(op):
    def make(monkeypatch):
        from cleisthenes_tpu.ops import modmath

        monkeypatch.setattr(modmath.ModEngine, "HOST_FLOOR", 16)
        eng = modmath.get_engine("tpu")
        g = eng.group.g

        def call(rows):
            if op == "pow":
                eng.pow_batch([g] * rows, [3] * rows)
            elif op == "dual_pow":
                eng.dual_pow_batch(
                    [g] * rows, [3] * rows, [g] * rows, [5] * rows
                )
            else:  # 8 shared-base groups: the comb's floor is 64 rows
                eng.pow_batch_grouped([(g, [3] * (rows // 8))] * 8)

        low, high = (8, 64) if op == "comb" else (8, 16)
        return lambda: call(low), lambda: call(high)

    return make


def _rs256(op):
    def make(monkeypatch):
        from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder

        monkeypatch.setattr(XlaErasureCoder, "HOST_FLOOR_BYTES", 256)
        coder = XlaErasureCoder(4, 2)

        def shards(batch, length):
            shape = (batch, 2, length) if batch else (2, length)
            return RNG.integers(0, 256, shape, dtype=np.uint8)

        lost = [1, 2]  # not the identity pattern
        calls = {
            "encode": lambda b, l: coder.encode(shards(0, l)),
            "decode": lambda b, l: coder.decode(lost, shards(0, l)),
            "encode_batch": lambda b, l: coder.encode_batch(shards(b, l)),
            "decode_batch": lambda b, l: coder.decode_batch(
                np.array([lost] * b), shards(b, l)
            ),
            "decode_recheck": lambda b, l: coder.decode_recheck_batch(
                np.array([lost] * b), shards(b, l)
            ),
        }
        # one instance under 256 bytes, a batch under 4 x 256
        return (
            lambda: calls[op](2, 32), lambda: calls[op](8, 256)
        )

    return make


def _rs65536(op):
    def make(monkeypatch):
        from cleisthenes_tpu.ops.rs16 import Xla16ErasureCoder

        coder = Xla16ErasureCoder(4, 2)
        one = RNG.integers(0, 256, (2, 32), dtype=np.uint8)
        many = RNG.integers(0, 256, (8, 2, 32), dtype=np.uint8)
        lost = [1, 2]
        if op == "encode":  # single instances never leave the host
            return lambda: coder.encode(one), None
        if op == "decode":
            return lambda: coder.decode(lost, one), None
        if op == "encode_batch":  # batches always run on the device
            return None, lambda: coder.encode_batch(many)
        return None, lambda: coder.decode_batch(np.array([lost] * 8), many)

    return make


def _merkle(op):
    def make(monkeypatch):
        from cleisthenes_tpu.ops.merkle import XlaMerkle

        monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_VERIFY", 16)
        monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_BUILD_LEAVES", 64)
        merkle = XlaMerkle()

        def call(batch):
            leaves = RNG.integers(0, 256, (batch, 4, 32), dtype=np.uint8)
            if op == "hash_batch":
                merkle._hash_batch(leaves[:, 0])
            elif op == "build_forest":
                merkle.build_batch(leaves)
            else:
                trees = merkle._host.build_batch(leaves)
                merkle.verify_batch(
                    np.stack([
                        np.frombuffer(t.root, dtype=np.uint8) for t in trees
                    ]),
                    leaves[:, 1],
                    np.stack([
                        np.frombuffer(b"".join(t.branch(1)), dtype=np.uint8)
                        .reshape(-1, 32)
                        for t in trees
                    ]),
                    np.ones(batch, dtype=np.uint32),
                )

        return lambda: call(4), lambda: call(16)

    return make


FAMILIES = {
    "modexp_12x22.pow": _modexp("pow"),
    "modexp_12x22.dual_pow": _modexp("dual_pow"),
    "modexp_12x22.comb": _modexp("comb"),
    "rs_gf256.encode": _rs256("encode"),
    "rs_gf256.decode": _rs256("decode"),
    "rs_gf256.encode_batch": _rs256("encode_batch"),
    "rs_gf256.decode_batch": _rs256("decode_batch"),
    "rs_gf256.decode_recheck": _rs256("decode_recheck"),
    "rs_gf65536.encode": _rs65536("encode"),
    "rs_gf65536.decode": _rs65536("decode"),
    "rs_gf65536.encode_batch": _rs65536("encode_batch"),
    "rs_gf65536.decode_batch": _rs65536("decode_batch"),
    "sha256.hash_batch": _merkle("hash_batch"),
    "merkle.build_forest": _merkle("build_forest"),
    "merkle.verify_branches": _merkle("verify_branches"),
}


def test_every_placement_family_has_a_case():
    import re

    noted = set()
    for path in (REPO / "cleisthenes_tpu" / "ops").glob("*.py"):
        if path.name == "placement.py":
            continue
        for arg in re.findall(
            r"placement\.(?:note|batch)\(\s*([^,]+),", path.read_text()
        ):
            if arg.startswith('"'):
                noted.add(arg.strip('"'))
            elif arg.startswith("self._family + "):
                noted.add("modexp_12x22" + arg.split('"')[1])
            else:  # the host side of .pow or .comb, noted above
                assert arg == 'f"{self._family}.{host_op}"', arg
    assert noted == set(FAMILIES)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_spans_on_both_sides_of_its_floor(family, session, monkeypatch):
    below, above = FAMILIES[family](monkeypatch)
    name = "ops/" + family
    if below is not None:
        FakeAnnotation.log = []
        below()
        mine = [n for n in span_tree(FakeAnnotation.log) if n[0] == name]
        assert mine and all(n[1]["on_device"] is False for n in mine)
        if family == "rs_gf256.decode_recheck":
            # the fusion's refusal: an empty span; the caller's three
            # steps then tally and span themselves
            assert all(n[2] == [] for n in mine)
        else:
            assert all(
                [c[0] for c in n[2]] == ["ops/host"] for n in mine
            )
    if above is not None:
        FakeAnnotation.log = []
        above()
        mine = [n for n in span_tree(FakeAnnotation.log) if n[0] == name]
        assert mine and all(n[1]["on_device"] is True for n in mine)
        for node in mine:
            kinds = [c[0] for c in node[2]]
            assert "ops/device" in kinds and "ops/host" not in kinds
            assert set(kinds) <= {"ops/pack", "ops/device", "ops/unpack"}
            device = [c for c in node[2] if c[0] == "ops/device"]
            assert all(c[1]["program"] for c in device)
            assert node[1]["items"] > 0
    tally = placement.snapshot()[family]
    assert trace.totals()[name]["calls"] == (
        tally["device_calls"] + tally["host_calls"]
    )
    assert (tally["host_calls"] > 0) == (below is not None)
    assert (tally["device_calls"] > 0) == (above is not None)


# ---------------------------------------------------------------------------
# a share wave as byte columns: the same spans, and args that say so
# ---------------------------------------------------------------------------


def _find(nodes, name):
    out = []
    for node in nodes:
        if node[0] == name:
            out.append(node)
        out.extend(_find(node[2], name))
    return out


@pytest.mark.parametrize("side", ["device", "host"])
def test_columnar_wave_opens_the_spans_the_metrics_read(
    side, session, monkeypatch
):
    """tpke_host_pct reads tpke/issue_batch, cp_challenge and
    verify_combine_batch; ops_marshal_pct / ops_device_wait_pct /
    ops_host_kernel_pct read ops/pack, unpack, device, host.  The
    columnar path opens all of them, with ``columnar`` and
    ``materialized`` on the two batch spans and the tally beside."""
    from cleisthenes_tpu.ops import modmath, tpke

    monkeypatch.setattr(
        modmath.ModEngine, "HOST_FLOOR", 16 if side == "device" else 8192
    )
    pub, secs = tpke.deal(n=4, threshold=2, seed=71)
    wave = tpke.ShareWave(
        secs,
        [pub.verification_keys[s.index - 1] for s in secs],
        [(tpke.hash_to_group(b"sp|%d" % j), b"sp|%d" % j) for j in range(8)],
    )
    tpke.reset_share_tally()
    tpke._COMBINE_MEMO.clear()
    cols = tpke.issue_share_columns([wave], backend="tpu")
    groups = [
        (pub, base, cols[j * 4 : j * 4 + 3], ctx)
        for j, (base, ctx) in enumerate(wave.pairs)
    ]
    verdicts, values, _ = tpke.verify_and_combine_share_groups(
        groups, 2, backend="tpu"
    )
    assert all(all(v) for v in verdicts) and None not in values
    assert tpke.share_tally() == {
        "shares_issued_columnar": 32,
        "shares_issued_listed": 0,
        "shares_materialized": 0,
    }
    tree = span_tree(FakeAnnotation.log)
    assert [n[0] for n in tree] == [
        "tpke/issue_batch", "tpke/verify_combine_batch",
    ]
    issue, verify = tree
    assert issue[1]["items"] == 32
    assert issue[1]["columnar"] is True and issue[1]["materialized"] == 0
    assert verify[1]["columnar"] is True and verify[1]["materialized"] == 0
    assert verify[1]["groups"] == 8
    for node, family, items in (
        (issue, "ops/modexp_12x22.comb", 96),
        (verify, "ops/modexp_12x22.dual_pow", 2 * 24 + 8 * 2),
    ):
        assert len(_find([node], "tpke/cp_challenge")) == 1
        fams = _find([node], family)
        assert sum(f[1]["items"] for f in fams) == items
        for fam in fams:
            kinds = {c[0] for c in fam[2]}
            if side == "device":
                assert fam[1]["on_device"] is True
                assert "ops/device" in kinds
                assert kinds <= {"ops/pack", "ops/device", "ops/unpack"}
            else:
                assert fam[1]["on_device"] is False
                assert kinds == {"ops/host"}
    if side == "device":  # the comb packs and unpacks inside its batch
        assert _find([issue], "ops/pack") and _find([issue], "ops/unpack")
    # the list entry point says what it is, and what it made
    FakeAnnotation.log = []
    tpke.issue_shares_batch(
        [(secs[0], wave.pairs[0][0], b"x", wave.vks[0])], backend="tpu"
    )
    (listed,) = span_tree(FakeAnnotation.log)
    assert listed[0] == "tpke/issue_batch"
    assert listed[1]["columnar"] is False and listed[1]["materialized"] == 1
    assert tpke.share_tally()["shares_issued_listed"] == 1
    # a materialised slice is counted, call by call
    FakeAnnotation.log = []
    tpke.verify_and_combine_share_groups(
        [groups[0][:2] + (groups[0][2].to_shares(),) + groups[0][3:]],
        2,
        backend="tpu",
    )
    (mixed,) = span_tree(FakeAnnotation.log)
    assert mixed[1]["columnar"] is False and mixed[1]["materialized"] == 0
    assert tpke.share_tally()["shares_materialized"] == 1 + 3


# ---------------------------------------------------------------------------
# the lockstep executor under a real profiler session, on the CPU platform
# ---------------------------------------------------------------------------


def _contains(outer, inner):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_lockstep_epoch_in_a_real_profile(tmp_path, monkeypatch):
    import jax.profiler

    from benchmarks import trace_reduce
    from cleisthenes_tpu.ops.merkle import XlaMerkle
    from cleisthenes_tpu.ops.modmath import ModEngine
    from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder
    from cleisthenes_tpu.protocol.spmd import LockstepCluster

    # toy batches sit under every floor: pin them to the XLA kernels
    monkeypatch.setattr(ModEngine, "host_delegation", False)
    # a collection between the epoch's spans would lie outside the epoch
    monkeypatch.setattr(trace, "_hook_collector", lambda: None)
    monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_VERIFY", 0)
    monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_BUILD_LEAVES", 0)
    monkeypatch.setattr(XlaErasureCoder, "HOST_FLOOR_BYTES", 0)
    cluster = LockstepCluster(
        config=Config(n=4, batch_size=8, crypto_backend="tpu"), key_seed=3
    )

    def epoch():
        for i in range(8):
            cluster.submit(b"tx-%d-%d" % (cluster.epoch, i), cluster.ids[i % 4])
        return cluster.run_epoch()

    epoch()  # compiles; no session, so nothing is counted
    trace.reset_totals()
    assert trace.totals() == {}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        stats = epoch()
    finally:
        jax.profiler.stop_trace()
    got = trace.totals()
    trace.reset_totals()
    epoch()  # the session is over: the table stays empty
    assert trace.totals() == {}

    # every phase and the layers beneath are there
    for key in (
        "lockstep/epoch", "lockstep/propose", "lockstep/rbc_encode",
        "lockstep/rbc_verify", "lockstep/rbc_decode", "lockstep/bba",
        "lockstep/coin_wave", "lockstep/decrypt", "lockstep/commit",
        "tpke/issue_batch", "tpke/verify_combine_batch", "tpke/cp_challenge",
        "ops/pack", "ops/device", "ops/unpack", "ops/modexp_12x22.dual_pow",
    ):
        assert key in got, key
    assert got["lockstep/epoch"]["calls"] == 1
    assert got["lockstep/coin_wave"]["calls"] == stats["coin_waves"]
    assert got["tpke/issue_batch"]["calls"] == stats["coin_waves"]
    # self times partition the epoch, and the spans see all of it
    covered = sum(row["self_s"] for row in got.values())
    assert covered == pytest.approx(got["lockstep/epoch"]["total_s"])
    assert covered >= 0.95 * stats["epoch_s"]
    assert got["ops/device"]["total_s"] < got["lockstep/epoch"]["total_s"]

    # and on the profiler's own timeline: the host plane of the xplane
    loaded = trace_reduce.load_xplane(str(tmp_path), set(got))
    events = [
        ev for plane in loaded["planes"] if plane["name"].startswith("/host:")
        for line in plane["lines"] for ev in line["events"]
    ]
    epochs = [ev for ev in events if ev[0] == "lockstep/epoch"]
    waits = [ev for ev in events if ev[0] == "ops/device"]
    assert len(epochs) == 1
    assert len(waits) == got["ops/device"]["calls"]
    assert all(_contains(epochs[0], ev) for ev in waits)
    # the operator's reading of the same profile
    assert tracetool.profile_span_names(str(tmp_path)) == set(got)
    reduced = tracetool.device_gaps(str(tmp_path), window="lockstep/epoch")
    assert reduced["window_s"] == pytest.approx(epochs[0][2] * 1e-9)
    report = tracetool.device_gaps_report(reduced)
    assert "window" in report
    # the waves stayed byte columns, and the profile says so
    tally = reduced["share_tally"]
    assert tally["shares_issued_columnar"] == (
        stats["coin_issues"] + stats["dec_issues"]
    )
    assert tally["shares_issued_listed"] == 0
    assert tally["shares_materialized"] == 0
    assert "issued_columnar %d" % tally["shares_issued_columnar"] in report


# ---------------------------------------------------------------------------
# the served path: new spans, and the ring as it was
# ---------------------------------------------------------------------------


def _seeded_cluster(trace_on: bool):
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    cluster = SimulatedCluster(
        config=Config(n=4, batch_size=8, seed=1234, trace=trace_on),
        seed=1234,
        key_seed=1,
    )
    for i in range(24):
        cluster.submit(b"tx-%04d" % i)
    cluster.run_epochs()
    cluster.assert_agreement()
    return cluster


def test_served_turn_spans_nest_under_a_session(session):
    cluster = _seeded_cluster(trace_on=False)
    assert all(hb.trace is None for hb in cluster.nodes.values())
    got = trace.totals()
    for key in (
        "hb/on_idle", "hb/coin_drain", "hb/settler", "hb/pipeline",
        "hb/deferred", "hb/dec_drain", "hb/start_epoch", "hub/flush",
        "hub/drain", "hub/branches", "hub/decodes", "hub/shares",
        "hub/callbacks",
        "transport/step_wave", "transport/frame_decode",
        "transport/mac_verify_batch", "transport/frame_encode",
        "transport/flush", "router/route", "coin/issue_batch",
        "rbc/propose", "tpke/encrypt", "settle/dec_share_batch",
        "settle/combine",
    ):
        assert key in got, (key, sorted(got))
    assert all(key.split("/")[0] in trace.CATEGORIES for key in got)
    parents: dict = {}

    def walk(nodes, parent):
        for name, _args, children in nodes:
            parents.setdefault(name, set()).add(parent)
            walk(children, name)

    walk(span_tree(FakeAnnotation.log), None)
    assert parents["hb/deferred"] == {"hb/on_idle"}
    assert parents["hub/flush"] <= {"hb/deferred", "router/route", None}
    assert parents["hub/shares"] == {"hub/flush"}
    assert parents["router/route"] == {"transport/step_wave"}
    assert parents["transport/frame_decode"] == {"transport/step_wave"}
    assert parents["transport/mac_verify_batch"] == {"transport/step_wave"}
    assert parents["coin/issue_batch"] == {"hb/coin_drain"}


def test_seeded_ring_is_what_it_was_before_the_spans():
    """The digest of the parent commit's ring for this seeded run
    (node, seq, instant or span, cat, name, sorted args): the scoped
    sites moved to trace.span and the new spans stayed out of it."""
    cluster = _seeded_cluster(trace_on=True)
    h = hashlib.sha256()
    count = 0
    events_by_node = cluster.trace_events()
    for node in sorted(events_by_node):
        for seq, _ts, dur, cat, name, args in events_by_node[node]:
            count += 1
            h.update(repr(
                (node, seq, dur is None, cat, name, sorted(args.items()))
            ).encode())
    assert count == 1433
    assert h.hexdigest() == (
        "15d659194e6d6729c07077b55b0585c99a16002310bb8372e1a7bb2c23cde4b4"
    )
