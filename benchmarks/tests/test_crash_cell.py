"""PR 36's fault schedule and its cell, ``n16-b4k-crash5.steady``: the
files load by name; toy copies (N=4 f=1, N=7 f=2) run through a kill
and a restart and come level; the events fire at a delivery-wave
boundary inside a round, in order; the stamps follow the validators in
service; fail-over and the resubmission keep one attempt a transaction;
each number of the reference reads 0 on a sound run and over 0 under its
fault, on observations made by hand too; a traffic file without
``faults`` gives the result line it gave before; and the next deployment
with faults is new files only.  (A new file beside test_harness.py,
whose fixtures and helpers it borrows.)"""

import copy
import json

import pytest

from benchmarks import reference
from benchmarks.tests.test_harness import (  # noqa: F401
    _boundary_the_trace_starts_at,
    add_cell,
    add_config,
    harness,
    toy_root,
)
from benchmarks.tests.test_wal_cell import (  # noqa: F401
    WAL_NUMBERS,
    _logged_obs,
    _rewrite,
    wal_root,
)

CELL = "n16-b4k-crash5.steady"
TWIN = "n16-b4k-wal.steady"
SERVED_NUMBERS = ["lost", "duplicated", "foreign", "misplaced", "forked",
                  "unsettled", "oversize"]
OUTAGE_METRICS = ["degraded_settle_p50_ms", "service_gap_ms",
                  "degraded_waves_per_round", "wal_replay_ms",
                  "catchup_rounds", "resubmitted_tx", "recover_s",
                  "outage_settle_p90_ms"]
# what the outage moves is the tail, and the cell's one tail under a
# bound is ``settle_p99_ms``; the medians are moved by the degraded
# stretch alone
MOVE_THE_MEDIAN = {"degraded_settle_p50_ms", "degraded_waves_per_round"}
# the twin's two readers that move ``settle_p90_ms``, which the cell
# does not report (PERF.md section 2), and their stand-ins here, which
# move ``settle_p99_ms``
NOT_LISTED = {"gen_late_p95_ms", "compiles_in_window.lat"}
STAND_INS = ["compiles_in_window.crash", "gen_late_p95_ms.crash"]
SEED = 2**31 + 36


def _ids(n):
    return [f"node{i:03d}" for i in range(n)]


def add_crash_cell(root, n, name="toy-crash", traffic=None, restart=True,
                   rate=300):
    """A toy copy of the crash configuration and of its cell: the last
    f validators killed at 0.3 of the window, restarted at 0.6; a
    checkpoint every 4 commits, so that a restart replays one."""
    f = (n - 1) // 3
    add_config(root, name, "n16-b4k-crash5",
               {"n": n, "batch_size": 16 * n, "ledger_checkpoint_every": 4})
    path = root / f"benchmarks/configs/{name}.json"
    cfg = json.loads(path.read_text())
    cfg["durable_replicas"] = n - f
    path.write_text(json.dumps(cfg))
    dead = _ids(n)[-f:]
    faults = [{"at": 0.3, "kill": dead}]
    if restart:
        faults.append({"at": 0.6, "restart": dead})
    fields = {"rate_tx_per_s": rate, "faults": faults}
    fields.update(traffic or {})
    add_cell(root, f"{name}.steady", CELL, name, fields)
    return f"{name}.steady", [n - f + i for i in range(f)]


def _kept(into):
    """A ``fault=`` hook that breaks nothing and keeps the executor."""
    def keep(executor):
        into["executor"] = executor
    return keep


# -- the files ---------------------------------------------------------------


def test_the_configuration_and_its_cell_are_the_twins_with_an_outage():
    from benchmarks import faults, spec
    from benchmarks.executors import fault_schedule

    cell, twin = spec.load_cell(CELL), spec.load_cell(TWIN)
    assert cell.chips == 1
    for key in ("executor", "tx_bytes", "config", "cluster", "durable_after"):
        assert cell.config[key] == twin.config[key], key
    assert (twin.config["durable_replicas"],
            cell.config["durable_replicas"]) == (16, 11)
    assert list(cell.config["reduced"]) == ["wal_disks", "fold"]
    for key, text in twin.config["assumed"].items():
        assert cell.config["assumed"][key] == text
    assert {"faults", "which_five", "restart", "clients", "in_service"} <= set(
        cell.config["assumed"]
    )
    mine = {"clients_return": "in_service"}
    shape = [k for k in twin.traffic if k not in ("who", "why")]
    assert {k: cell.traffic[k] for k in shape} == {
        k: twin.traffic[k] for k in shape
    }
    assert {k: cell.traffic[k] for k in mine} == mine
    assert set(cell.traffic) == set(twin.traffic) | set(mine) | {"faults"}
    five = _ids(16)[11:]
    assert cell.traffic["faults"] == [
        {"at": 8 / 15, "kill": five}, {"at": 12 / 15, "restart": five},
    ]
    schedule = fault_schedule(cell.traffic, _ids(16), 5)
    assert [(ev.kind, ev.members) for ev in schedule.events] == [
        ("kill", (11, 12, 13, 14, 15)), ("restart", (11, 12, 13, 14, 15)),
    ]
    assert fault_schedule(twin.traffic, _ids(16), 5) is None
    # the twin's medians and its 99th percentile, which is what the
    # restart moves; the 90th and the time to recover swing by more than
    # a bound admits and stand among the per-layer metrics
    assert [m["name"] for m in cell.end_to_end] == [
        "settle_p50_ms", "settle_p99_ms", "order_p50_ms", "setup_s",
    ]
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in twin.per_layer if m["name"] not in NOT_LISTED
    ] + OUTAGE_METRICS + STAND_INS
    reported = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in reported, m
    for m in cell.per_layer[-len(OUTAGE_METRICS + STAND_INS):]:
        assert m["workloads"] == [CELL]
        assert m["layer"] == "fault schedule" or m["name"] in STAND_INS
        assert m["moves"] == ("settle_p50_ms" if m["name"] in MOVE_THE_MEDIAN
                              else "settle_p99_ms")
        assert m["source"] in ("host_clock", "program_counter")
        spec.load_reader(m["name"])
    # the control breaks the outage only where there is one
    assert sorted(faults.for_cell(cell)) == sorted(
        list(faults.for_cell(twin)) + list(faults.OUTAGE_FAULTS)
    )
    assert not set(faults.OUTAGE_FAULTS) & set(faults.for_cell(twin))


@pytest.mark.parametrize("traffic,match", [
    ({"loop": "backlog", "backlog_batches": 2}, "open loop"),
    ({"faults": [{"at": 0.5, "kill": ["node009"]}]}, "roster lacks"),
    ({"faults": [{"at": 0.5, "restart": ["node003"]}]}, "that is up"),
    ({"faults": [{"at": 0.5, "kill": ["node002", "node003"]}]}, "tolerates 1"),
    ({"faults": [{"at": 0.5, "kill": ["node003"]},
                 {"at": 0.4, "restart": ["node003"]}]}, "in order of time"),
    ({"faults": [{"at": 0.5, "kill": ["node003"], "why": "x"}]}, "a fault is"),
    ({"faults": [{"at": 0.2, "kill": ["node003"]},
                 {"at": 0.5, "kill": ["node003"]}]}, "that is down"),
    ({"clients_return": "whenever"}, "clients_return"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_schedule_a_run_cannot_apply_is_refused(traffic, match):
    from benchmarks.executors import fault_schedule
    from benchmarks.spec import SpecError

    sound = {"loop": "open", "faults": [{"at": 0.3, "kill": ["node003"]},
                                        {"at": 0.6, "restart": ["node003"]}]}
    assert fault_schedule(sound, _ids(4), 1) is not None
    with pytest.raises(SpecError, match=match):
        fault_schedule(dict(sound, **traffic), _ids(4), 1)


def test_a_restart_needs_a_log_and_is_refused_before_warm_up(
    harness, toy_root
):
    from benchmarks.spec import SpecError

    add_cell(toy_root, "toy-served.crash", "n16-b4k.steady", "toy-served", {
        "rate_tx_per_s": 300,
        "faults": [{"at": 0.3, "kill": ["node003"]},
                   {"at": 0.6, "restart": ["node003"]}],
    })
    with pytest.raises(SpecError, match="wal_dir"):
        harness.run_cell("toy-served.crash", SEED, 1.5, False, root=toy_root)


# -- toy copies, end to end --------------------------------------------------


@pytest.mark.parametrize("n", [4, 7])
@pytest.mark.parametrize("trace", [False, True])
def test_a_toy_copy_is_killed_restarted_and_comes_level(
    harness, toy_root, n, trace
):
    cell, dead = add_crash_cell(toy_root, n)
    kept = {}
    result = harness.run_cell(cell, SEED, 3.0, trace, root=toy_root,
                              fault=_kept(kept))
    compared = result["compared"]
    assert result["correct"] is True, compared
    assert list(compared) == (
        SERVED_NUMBERS + ["killed_proposed"] + WAL_NUMBERS
    )
    assert all(row == {"value": 0, "limit": 0} for row in compared.values())
    assert result["attempted"] == 900 and result["failed"] == 0
    assert not (toy_root / ".bench_wal").exists()
    ex = kept["executor"]
    ids = _ids(n)
    # all n ledgers are level at rest, the restarted ones included
    assert [o["node"] for o in ex.outages] == [ids[i] for i in dead]
    for o in ex.outages:
        assert o["t_kill"] < o["t_restart"] < o["t_in_service"]
        # it came back from its log: everything it had settled, and a
        # checkpoint every 4 commits among it
        assert o["settled_at_restart"] == o["settled_at_kill"] >= 4
        assert o["settled_in_service"] > o["settled_at_restart"]
        assert o["replay_s"] > 0
    if trace:
        metrics = result["metrics"]
        assert set(OUTAGE_METRICS + STAND_INS) <= set(metrics)
        last = max(o["t_in_service"] for o in ex.outages)
        assert metrics["recover_s"]["value"] == pytest.approx(
            last - ex.fault_log[1]["t"]
        )
        assert metrics["outage_settle_p90_ms"]["value"] > 0
        # the restart and what the clients send after it are traced
        assert metrics["ingress_submit_span_us"]["value"] > 0
        assert metrics["compiles_in_window.crash"]["value"] == 0
        assert metrics["gen_late_p95_ms.crash"]["value"] > 0
        assert metrics["wal_replay_ms"]["value"] > 0
        assert metrics["catchup_rounds"]["value"] >= 1
        assert metrics["degraded_waves_per_round"]["value"] > 0
        assert metrics["degraded_settle_p50_ms"]["value"] > 0
        assert metrics["resubmitted_tx"]["value"] == ex.resubmitted
        assert "wal_bytes_per_epoch" in metrics
    else:
        assert sorted(result["metrics"]) == [
            "order_p50_ms", "settle_p50_ms", "settle_p99_ms", "setup_s",
        ]
        assert (result["metrics"]["settle_p99_ms"]["value"]
                >= result["metrics"]["settle_p50_ms"]["value"])
        assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)


@pytest.fixture
def outage(harness, toy_root):
    """One sound toy run at N=7 f=2, and its executor as it ended."""
    cell, dead = add_crash_cell(toy_root, 7)
    kept = {}
    result = harness.run_cell(cell, SEED + 1, 3.0, False, root=toy_root,
                              fault=_kept(kept))
    assert result["correct"] is True, result["compared"]
    return result, kept["executor"], dead


def test_events_fire_at_a_wave_boundary_inside_a_round_and_in_order(outage):
    _result, ex, dead = outage
    kill, restart = ex.fault_log
    assert (kill["kind"], restart["kind"]) == ("kill", "restart")
    assert kill["nodes"] == restart["nodes"] == [ex.ids[i] for i in dead]
    assert kill["t"] < restart["t"]
    for ev, share in ((kill, 0.3), (restart, 0.6)):
        assert ev["due_s"] == pytest.approx(share * 3.0)
        # at the first boundary at or after its time: not before, and
        # not a round late (a toy round is some tens of milliseconds)
        t0 = ex.due[-1] - 3.0  # the last arrival is due as the window closes
        assert 0 <= ev["t"] - (t0 + ev["due_s"]) < 0.5
        start, end, _bytes = ex.round_log[ev["round"]]
        if ev["wave"]:
            # between two delivery waves of that round: the epoch the
            # round started is in flight
            assert start <= ev["t"] <= end
            assert 1 <= ev["wave"] <= ex.round_waves[ev["round"]] > 2
        else:
            # the toy was idle, waiting for an arrival: taken at once,
            # before the next round's first wave
            assert ex.round_log[ev["round"] - 1][1] <= ev["t"] <= start
    assert kill["epochs_settled"] < restart["epochs_settled"]


def test_the_stamps_follow_the_validators_in_service(outage):
    _result, ex, dead = outage
    kill, restart = ex.fault_log
    n = len(ex.ids)
    rows = ex.served_at_settle
    assert len(rows) == len(ex.t_settled) == len(ex.held_at_settle)
    for epoch, (at, row) in enumerate(zip(ex.t_settled, rows)):
        down = [i for i in range(n) if not row[i]]
        if at <= kill["t"]:
            assert down == [], epoch
        else:
            assert set(down) <= set(dead)
    # the others went on stamping while the dead were down ...
    degraded = [e for e, at in enumerate(ex.t_settled)
                if kill["t"] < at <= restart["t"]]
    assert len(degraded) >= 3
    assert all(not rows[e][i] for e in degraded for i in dead)
    # ... every one of those epochs, and the dead crossed none of them
    assert degraded == list(range(degraded[0], degraded[-1] + 1))
    for i in dead:
        (o,) = [o for o in ex.outages if o["node"] == ex.ids[i]]
        assert o["settled_at_kill"] <= degraded[0]
        assert o["settled_in_service"] > degraded[-1]
        back = [e for e, row in enumerate(rows)
                if row[i] and ex.t_settled[e] > restart["t"]]
        assert back and back[0] >= o["settled_in_service"] - 1
    assert ex.t_settled == sorted(ex.t_settled)
    assert ex.t_ordered == sorted(ex.t_ordered)


def test_fail_over_and_resubmission_keep_one_attempt_a_transaction(outage):
    result, ex, dead = outage
    kill, restart = ex.fault_log
    n = len(ex.ids)
    gone = {ex.ids[i] for i in dead}
    # one attempt a transaction, timed from the moment it was first due
    assert result["attempted"] == len(ex.timed) == len(set(ex.timed)) == 900
    assert ex.due == sorted(ex.due) and len(ex.due) == 900
    timed = set(ex.timed)
    rows = [r for r in ex.submissions if r[0] in timed]
    assert len(rows) == 900 + ex.resubmitted and ex.resubmitted > 0
    assert kill["resubmitted"] == ex.resubmitted
    # while they were down and until they were level again, nothing was
    # sent to the dead; before and after, a transaction goes to its home
    first_back = min(o["submissions_in_service"] for o in ex.outages)
    outage_rows = ex.submissions[kill["submissions"]:first_back]
    assert outage_rows and not [r for r in outage_rows if r[1] in gone]
    assert [r for r in ex.submissions[first_back:] if r[1] in gone]
    # what was sent again had been acknowledged by a dead validator
    # alone, went to the living round robin, and still counts once
    again = ex.submissions[kill["submissions"]:
                           kill["submissions"] + ex.resubmitted]
    homes = {}
    for tx, nid, ok in ex.submissions[:kill["submissions"]]:
        if ok:
            homes.setdefault(tx, set()).add(nid)
    assert all(homes[tx] <= gone for tx, _nid, _ok in again)
    living = [nid for nid in ex.ids if nid not in gone]
    assert [nid for _tx, nid, _ok in again] == [
        living[k % len(living)] for k in range(len(again))
    ]
    # what was refused spreads over the living validators
    first_at = {}
    for tx, nid, _ok in rows:
        first_at.setdefault(tx, nid)
    rerouted = {
        first_at[a.tx] for a in ex._arrivals
        if ex.ids[a.nonce % n] in gone and first_at[a.tx] not in gone
    }
    assert len(rerouted) > 1


def test_the_client_of_a_dead_validator_tries_another_at_once():
    from benchmarks import executors

    ex = object.__new__(executors.Served)
    ex.ids = _ids(7)
    ex._answering = [0, 1, 3, 4]
    # the refused share spreads over the validators that answer
    got = [ex._fail_over(nonce) for nonce in range(5, 5 + 7 * 8, 7)]
    assert got == [0, 1, 3, 4, 0, 1, 3, 4]


@pytest.mark.parametrize("clients_return", ["in_service", "at_restart"])
def test_clients_go_back_when_the_traffic_file_says(
    harness, toy_root, clients_return
):
    """``at_restart``: when the port opens, which at this commit loses
    what the restarted validator admits before it is level (PERF.md
    section 7); the harness routes it all the same."""
    cell, dead = add_crash_cell(
        toy_root, 4, traffic={"clients_return": clients_return}, rate=2000,
    )
    kept = {}
    result = harness.run_cell(cell, SEED, 2.0, False, root=toy_root,
                              fault=_kept(kept))
    ex = kept["executor"]
    victim = ex.ids[dead[0]]
    restart = ex.fault_log[1]
    (o,) = ex.outages
    early = [r for r in ex.submissions[
        restart["submissions"]:o["submissions_in_service"]] if r[1] == victim]
    if clients_return == "in_service":
        assert not early
        assert result["correct"] is True, result["compared"]
    else:
        assert early
        over = {k for k, v in result["compared"].items() if v["value"] > 0}
        assert over <= {"lost"}, result["compared"]


def test_a_kill_with_no_restart_is_held_over_those_that_are_left(
    harness, toy_root
):
    """The source's own shape, and what the cell falls back to if the
    program cannot do the restart: the dead stay down, and the ledgers
    and frontiers at rest are those of the living."""
    cell, dead = add_crash_cell(toy_root, 4, restart=False)
    kept = {}
    result = harness.run_cell(cell, SEED, 2.0, True, root=toy_root,
                              fault=_kept(kept))
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    assert "killed_proposed" in result["compared"]
    ex = kept["executor"]
    assert [ev["kind"] for ev in ex.fault_log] == ["kill"]
    metrics = result["metrics"]
    assert not {"wal_replay_ms", "catchup_rounds"} & set(metrics)
    assert {"degraded_settle_p50_ms", "resubmitted_tx"} <= set(metrics)
    assert not (toy_root / ".bench_wal").exists()


# -- each fault of the outage reads its number -------------------------------


@pytest.mark.parametrize("name,caught_by", [
    ("resubmit_left_out", "lost"),
    ("restart_behind", "forked"),
    ("kill_not_taken", "killed_proposed"),
])
def test_a_fault_of_the_outage_reads_not_correct(
    harness, toy_root, name, caught_by
):
    from benchmarks import faults, spec

    cell, _dead = add_crash_cell(toy_root, 7)
    fault = faults.for_cell(spec.load_cell(cell, toy_root))[name]
    result = harness.run_cell(cell, SEED + 2, 3.0, False, root=toy_root,
                              fault=fault)
    assert result["correct"] is False
    row = result["compared"][caught_by]
    assert row["value"] > row["limit"], result["compared"]
    if name == "restart_behind":
        assert result["failed"] >= 1
    assert not (toy_root / ".bench_wal").exists()


def test_a_kill_only_cell_has_no_restart_to_break(toy_root):
    from benchmarks import faults, spec

    cell, _dead = add_crash_cell(toy_root, 4, restart=False)
    have = faults.for_cell(spec.load_cell(cell, toy_root))
    assert "restart_behind" not in have
    assert {"resubmit_left_out", "kill_not_taken"} <= set(have)


# -- the reference, on observations made by hand -----------------------------


def _outage_obs():
    """Four validators, six epochs of four transactions; node003 is
    killed with epoch 2 in flight (its own ordered frontier 2), epochs
    3 and 4 are ordered without it, and it is restarted after 5 epochs
    were stamped ordered.  What it had admitted and not seen settled
    (t09, t10) was sent again to node000 and node001."""
    ids = _ids(4)
    txs = [b"t%02d" % i + bytes(13) for i in range(24)]
    subs = [(tx, ids[i % 4], True) for i, tx in enumerate(txs)]
    ledger = []
    for epoch in range(6):
        lo = 4 * epoch
        ledger.append({
            ids[i % 4]: [txs[i]] for i in range(lo, lo + 4)
        })
    # epochs 3 and 4: node003 is down; its clients went elsewhere
    for epoch, tx in ((3, txs[15]), (4, txs[19])):
        del ledger[epoch]["node003"]
        ledger[epoch]["node000"].append(tx)
    subs[15] = (txs[15], "node000", True)
    subs[19] = (txs[19], "node000", True)
    obs = {
        "node_ids": ids,
        "submissions": subs,
        "ledgers": {nid: copy.deepcopy(ledger) for nid in ids},
        "evicted": 0,
        "ordered": dict.fromkeys(ids, 6),
        "settled": dict.fromkeys(ids, 6),
        "batch_size": 8,
        "faults": {
            "pipeline_depth": 1,
            "down_at_rest": [],
            "outages": [{"node": "node003", "ordered_at_kill": 2,
                         "ordered_epochs_at_restart": 5}],
        },
    }
    return obs, txs


def _sound(obs):
    got = reference.compare_served(obs)
    assert list(got) == SERVED_NUMBERS + ["killed_proposed"]
    assert reference.verdict(got) and not any(v for v, _ in got.values())


def test_a_transaction_sent_again_counts_once_and_has_two_homes():
    obs, txs = _outage_obs()
    _sound(obs)
    # t11, admitted by node003, was sent again to node001 at the kill
    # and settled there in epoch 3: two homes, settled once
    for nid in obs["node_ids"]:
        ledger = obs["ledgers"][nid]
        ledger[2]["node003"].remove(txs[11])
        ledger[3]["node001"].append(txs[11])
    obs["submissions"].append((txs[11], "node001", True))
    _sound(obs)
    # had only node003 acknowledged it, node001 had no business with it
    obs["submissions"].pop()
    got = reference.compare_served(obs)
    assert got["misplaced"] == (1, 0) and got["lost"] == (0, 0)
    # settled at both homes: twice
    obs["submissions"].append((txs[11], "node001", True))
    for nid in obs["node_ids"]:
        obs["ledgers"][nid][2]["node003"].append(txs[11])
    assert reference.compare_served(obs)["duplicated"] == (1, 0)


def test_what_only_the_dead_admitted_and_nobody_sent_again_is_lost():
    obs, txs = _outage_obs()
    obs["submissions"].append((b"t99" + bytes(13), "node003", True))
    got = reference.compare_served(obs)
    assert got["lost"] == (1, 0) and not reference.verdict(got)


def test_a_dead_validator_that_goes_on_proposing_is_counted():
    obs, txs = _outage_obs()
    _sound(obs)
    # epoch 2 was in flight when it was killed (its own ordered
    # frontier, pipeline_depth 1): allowed.  Epoch 4 is not
    for nid in obs["node_ids"]:
        ledger = obs["ledgers"][nid][4]
        ledger["node003"] = [ledger["node000"].pop()]
    obs["submissions"][19] = (txs[19], "node003", True)
    got = reference.compare_served(obs)
    assert got["killed_proposed"] == (1, 0) and not reference.verdict(got)
    assert all(got[k] == (0, 0) for k in SERVED_NUMBERS)
    # a deeper pipeline may have had that epoch in flight too
    obs["faults"]["pipeline_depth"] = 3
    _sound(obs)
    obs["faults"]["pipeline_depth"] = 1
    # after its restart it proposes again, as it should
    obs["faults"]["outages"][0]["ordered_epochs_at_restart"] = 4
    _sound(obs)
    # one that is never restarted is held to the end: epochs 4 and 5
    del obs["faults"]["outages"][0]["ordered_epochs_at_restart"]
    assert reference.compare_served(obs)["killed_proposed"] == (2, 0)


def test_a_restarted_validator_that_is_not_level_is_forked():
    obs, _txs = _outage_obs()
    behind = obs["ledgers"]["node003"]
    del behind[4:]
    obs["settled"]["node003"] = obs["ordered"]["node003"] = 4
    assert reference.compare_served(obs)["forked"] == (2, 0)
    # ... or level with something else
    obs, txs = _outage_obs()
    obs["ledgers"]["node003"][4]["node001"] = [txs[17][:-1] + b"\x01"]
    assert reference.compare_served(obs)["forked"] == (1, 0)
    # only one the schedule leaves down is left out, and then another
    # validator is the witness
    obs, _txs = _outage_obs()
    del obs["ledgers"]["node003"][3:]
    obs["settled"]["node003"], obs["ordered"]["node003"] = 3, 4
    got = reference.compare_served(obs)
    assert got["forked"] == (3, 0) and got["unsettled"] == (1, 0)
    obs["faults"]["down_at_rest"] = ["node003"]
    del obs["faults"]["outages"][0]["ordered_epochs_at_restart"]
    for nid in obs["node_ids"][:3]:
        # ... and then it proposed nothing after it was killed
        del obs["ledgers"][nid][5]["node003"]
    obs["submissions"][23] = (obs["submissions"][23][0], "node003", False)
    _sound(obs)
    assert reference.witness(obs) == "node000"
    obs["faults"]["down_at_rest"] = ["node000"]
    assert reference.witness(obs) == "node001"
    assert reference.settled_epochs(obs)


def _outage_logs(tmp_path):
    """test_wal_cell's three sound logs, with node002 killed after
    epoch 0 and restarted: it was not in service when epochs 1 and 2
    were stamped settled, and took both over from its peers."""
    obs, records = _logged_obs(tmp_path)
    for nid, log in obs["wal"]["logs"].items():
        down = nid == "node002"
        log["served_at_settle"] = [True, not down, not down]
        log["adopted"] = [[1, 3]] if down else []
    obs["wal"]["durable_replicas"] = 2
    return obs, records


def _wal_values(obs):
    return {k: v for k, (v, _limit) in reference.compare_wal(obs).items()}


def test_a_log_is_held_to_the_stamps_of_its_validators_time_in_service(
    tmp_path
):
    obs, records = _outage_logs(tmp_path)
    sound = dict.fromkeys(WAL_NUMBERS, 0)
    assert _wal_values(obs) == sound
    # its records of epochs 1 and 2 came after those stamps (it adopted
    # them after its restart): late for nobody, since it was down
    log = obs["wal"]["logs"]["node002"]
    log["held_at_settle"][1] = log["held_at_settle"][2] = (
        log["held_at_settle"][0]
    )
    assert _wal_values(obs) == sound
    # ... but they do not count towards the replicas: two are left,
    # and with three asked for every transaction of those epochs is short
    obs["wal"]["durable_replicas"] = 3
    assert _wal_values(obs) == dict(sound, wal_short=12)
    obs["wal"]["durable_replicas"] = 2
    # had it been in service, it would have been late
    log["served_at_settle"] = [True, True, True]
    assert _wal_values(obs) == dict(sound, wal_late=2)
    # a validator in service is held to its stamps as before
    obs, records = _outage_logs(tmp_path)
    obs["wal"]["logs"]["node001"]["held_at_settle"][1] -= len(records[3])
    assert _wal_values(obs) == dict(sound, wal_late=1, wal_short=6)


def test_at_rest_a_restarted_validators_log_is_held_in_full(tmp_path):
    obs, records = _outage_logs(tmp_path)
    sound = dict.fromkeys(WAL_NUMBERS, 0)
    # an adopted epoch has its batch record and, as a rule, no ordered
    # record of the validator's own
    _rewrite(obs, "node002", records[:2] + records[3:])
    assert _wal_values(obs) == sound
    # ... but where there is one it comes first
    _rewrite(obs, "node002", records[:2] + [records[3], records[2]]
             + records[4:])
    assert _wal_values(obs) == dict(sound, wal_unordered=1)
    # an epoch it ordered itself needs its ordered record
    _rewrite(obs, "node002", records[1:])
    assert _wal_values(obs) == dict(sound, wal_unordered=1)
    # the batch it never got is missing, adopted or not
    _rewrite(obs, "node002", records[:6])
    assert _wal_values(obs) == dict(sound, wal_missing=1)
    # and one it got wrong is wrong
    obs["ledgers"]["node002"][1]["node000"][0] = b"\xff" * 16
    _rewrite(obs, "node002", records)
    assert _wal_values(obs) == dict(sound, wal_wrong=1)


# -- the readers, on stamps made by hand -------------------------------------


def _made_run():
    """A window of 1 s rounds from t=100: an epoch settles at the end
    of each; killed at 104.5, inside the fifth round; rounds of 1.5 s
    and three times the waves while down; restarted at 109.0; level
    again two rounds later."""
    rounds, waves, t = [], [], 100.0
    for k in range(12):
        width = 1.5 if 4 <= k < 8 else 1.0
        rounds.append((t, t + width, 0))
        waves.append(60 if 5 <= k < 7 else 20)
        t += width
    t_settled = [b for _a, b, _w in rounds]
    timed = [b"tx%02d" % k for k in range(12)]
    return {
        "t0": 100.0, "t_end": t, "rounds": rounds, "t_settled": t_settled,
        "timed": timed, "timed_ok": [True] * 12,
        "due": [a + 0.25 for a, _b, _w in rounds],
        "settled_in": {tx: k for k, tx in enumerate(timed)},
        "faults": {
            "events": [
                {"kind": "kill", "t": 104.5, "round": 4},
                {"kind": "restart", "t": 109.0, "round": 7},
            ],
            "outages": [
                {"node": "node002", "replay_s": 0.25, "round_restart": 7,
                 "round_in_service": 8, "t_restart_event": 109.0},
                {"node": "node003", "replay_s": 0.75, "round_restart": 7,
                 "round_in_service": 9, "t_restart_event": 109.0},
            ],
            "resubmitted": 37,
            "round_waves": waves,
            "never_back": [],
        },
    }


def test_the_readers_cut_the_run_at_the_kill_and_the_restart():
    from benchmarks import spec

    made = _made_run()
    read = {name: spec.load_reader(name) for name in OUTAGE_METRICS}
    # due at 105.75 and 107.25 (settled 107.0, 108.5), and 108.75
    # (settled 110.0): the median of 1,250 / 1,250 / 1,250 ms
    assert read["degraded_settle_p50_ms"](made) == pytest.approx(1250.0)
    # stamps 1 s apart before the kill; the longest gap that starts
    # while down is 1.5 s
    assert read["service_gap_ms"](made) == pytest.approx(500.0)
    assert read["degraded_waves_per_round"](made) == pytest.approx(3.0)
    assert read["wal_replay_ms"](made) == pytest.approx(500.0)
    assert read["catchup_rounds"](made) == 3.0
    assert read["resubmitted_tx"](made) == 37.0
    assert read["recover_s"](made) is None  # nobody is level yet
    made["faults"]["outages"][1]["t_in_service"] = 111.5
    made["faults"]["outages"][0]["t_in_service"] = 110.0
    assert read["recover_s"](made) == pytest.approx(2.5)
    # every transaction settled 1,250 ms after it was due, but for those
    # due in the rounds of 1 s: 750 ms
    assert read["outage_settle_p90_ms"](made) == pytest.approx(1250.0)
    # ... which is run.py's own reading of the same sample
    from benchmarks import run as harness_run

    assert harness_run.END_TO_END["settle_p90_ms"](made) == pytest.approx(
        read["outage_settle_p90_ms"](made)
    )
    made.pop("_latencies_ms")
    made["timed_ok"][3] = made["timed_ok"][5] = False  # refused: beyond
    assert read["outage_settle_p90_ms"](made) is None  # every percentile
    made["timed_ok"][3] = made["timed_ok"][5] = True
    # one that is never level again: no time to recover, no rounds
    made["faults"]["never_back"] = ["node003"]
    assert read["recover_s"](made) is None
    assert read["catchup_rounds"](made) is None
    # a run with no schedule: nothing to read
    del made["faults"]
    assert all(reader(made) is None for reader in read.values())


def test_the_trace_of_a_cell_with_a_schedule_starts_before_its_last_event(
    monkeypatch
):
    """The restart, its round and what the clients send after it are
    what a traced run of such a cell has to see: the trace starts at
    the first loop boundary within TRACE_SECONDS (or the longest round
    so far) of the schedule's last event, not of the window's end."""
    from benchmarks import run
    from benchmarks.executors import fault_schedule

    traffic = {"loop": "open", "faults": [
        {"at": 8 / 15, "kill": ["node003"]},
        {"at": 12 / 15, "restart": ["node003"]},
    ]}
    schedule = fault_schedule(traffic, _ids(4), 1)
    assert run.trace_anchor_s(45.0, schedule) == pytest.approx(36.0)
    assert run.trace_anchor_s(45.0, None) == 45.0
    kill_only = fault_schedule(dict(traffic, faults=traffic["faults"][:1]),
                               _ids(4), 1)
    assert run.trace_anchor_s(45.0, kill_only) == pytest.approx(24.0)
    # degraded rounds of 0.4 s, the longest round so far a checkpoint's
    # 1.2 s: the boundary at 33.6 s starts it, 2.4 s before the restart
    ticks = [32.8, 33.2, 33.6, 34.0, 35.9, 46.2]
    assert _boundary_the_trace_starts_at(
        monkeypatch, run.trace_anchor_s(45.0, schedule), 1.2, ticks
    ) == [33.6]


def test_a_last_round_that_is_the_longest_still_gets_a_trace(monkeypatch):
    """My chip run, PR 36, before the trace of such a cell started at
    its last event: the restart fell between two rounds at 36 s, the
    replays' round ended at 39.1 s and the catch-up's at 46.9 s, so no
    boundary fell in the stretch a trace started in and the traced run
    had no trace; ``run_open`` ticks once more as the window closes, and
    as a fallback the trace covers the drain."""
    ticks = [34.6, 35.1, 36.03, 39.1]
    assert _boundary_the_trace_starts_at(monkeypatch, 45.0, 0.85, ticks) == []
    assert _boundary_the_trace_starts_at(
        monkeypatch, 45.0, 0.85, ticks + [46.9]
    ) == [46.9]
    # a run whose trace a boundary started is not started twice
    assert _boundary_the_trace_starts_at(
        monkeypatch, 45.0, 0.85, [41.0, 42.6, 43.4, 45.2]
    ) == [42.6]


# -- without a schedule, nothing changed -------------------------------------


def test_a_traffic_file_without_faults_gives_the_line_it_gave_before(
    harness, wal_root
):
    """PR 33's cell at toy size: the keys of its result line, its
    metrics, its comparison and its device, as they were."""
    kept = {}
    lines = {}
    for trace in (False, True):
        result = harness.run_cell("toy-wal.steady", SEED, 2.0, trace,
                                  root=wal_root, fault=_kept(kept))
        assert result["correct"] is True, result["compared"]
        lines[trace] = result
        ex = kept["executor"]
        assert ex.faults is None and ex.fault_report() is None
        assert not ex.fault_log and not ex.outages and not ex.served_at_settle
        assert len(ex.round_waves) == ex.rounds
    assert list(lines[False]) == ["correct", "attempted", "failed", "metrics",
                                  "device", "compared"]
    assert list(lines[True]) == ["correct", "attempted", "failed", "metrics",
                                 "device", "breakdown", "compared"]
    assert list(lines[False]["metrics"]) == [
        "settle_p50_ms", "settle_p90_ms", "settle_p99_ms", "order_p50_ms",
        "setup_s",
    ]
    assert list(lines[True]["metrics"]) == [
        "gen_late_p95_ms", "ingress_submit_us_p50", "compiles_in_window.lat",
        "ingress_submit_span_us", "wal_pct", "wal_ms_per_commit",
        "wal_bytes_per_epoch", "wal_checkpoint_stall_ms",
    ]
    for result in lines.values():
        assert list(result["compared"]) == SERVED_NUMBERS + WAL_NUMBERS
    assert list(lines[False]["device"]) == [
        "platform", "kind", "count", "memory_peak_bytes"]
    assert list(lines[True]["device"]) == [
        "platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s"]


def test_the_observation_of_a_cell_without_faults_has_the_keys_it_had(
    harness, wal_root
):
    from benchmarks import executors

    seen = {}
    observe = executors.Served.observe

    def spy(self):
        seen["obs"] = observe(self)
        return seen["obs"]

    executors.Served.observe = spy
    try:
        harness.run_cell("toy-wal.steady", SEED, 1.5, False, root=wal_root)
    finally:
        executors.Served.observe = observe
    obs = seen["obs"]
    assert list(obs) == ["node_ids", "submissions", "ledgers", "evicted",
                         "ordered", "settled", "batch_size", "wal"]
    assert all(
        list(log) == ["path", "held_bytes", "held_at_settle"]
        for log in obs["wal"]["logs"].values()
    )


# -- the next deployment with faults is data ---------------------------------


def test_a_new_fault_schedule_is_new_files_only(harness, toy_root):
    """test_new_config_cell_and_metric_are_new_files_only, for faults:
    a configuration, a cell whose schedule kills one validator twice
    over, and a per-layer metric on the harness's record of it, added
    as files and entries; no file that was there is edited."""
    before = {
        p: p.read_bytes()
        for p in toy_root.rglob("*")
        if p.is_file() and p.name != "BENCHMARK.json"
    }
    add_config(toy_root, "toy-flap", "n16-b4k-crash5",
               {"n": 4, "batch_size": 64, "ledger_checkpoint_every": 4})
    path = toy_root / "benchmarks/configs/toy-flap.json"
    cfg = json.loads(path.read_text())
    cfg["durable_replicas"] = 3
    path.write_text(json.dumps(cfg))
    add_cell(toy_root, "toy-flap.steady", CELL, "toy-flap", {
        "rate_tx_per_s": 300,
        "faults": [
            {"at": 0.2, "kill": ["node001"]},
            {"at": 0.4, "restart": ["node001"]},
            {"at": 0.6, "kill": ["node001"]},
            {"at": 0.8, "restart": ["node001"]},
        ],
    })
    (toy_root / "benchmarks/layer_metrics/outage_s.py").write_text(
        "def read(run):\n"
        "    faults = run.get('faults')\n"
        "    if not faults:\n"
        "        return None\n"
        "    t = {}\n"
        "    for ev in faults['events']:\n"
        "        t.setdefault(ev['kind'], []).append(ev['t'])\n"
        "    return sum(b - a for a, b in zip(t['kill'], t['restart']))\n"
    )
    bench = json.loads((toy_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "outage_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "fault schedule",
        "moves": "settle_p50_ms", "workloads": ["toy-flap.steady"],
    })
    (toy_root / "BENCHMARK.json").write_text(json.dumps(bench))
    kept = {}
    result = harness.run_cell("toy-flap.steady", SEED, 3.0, True,
                              root=toy_root, fault=_kept(kept))
    assert result["correct"] is True, result["compared"]
    # the longest of the two recoveries, and the rounds it took
    assert 0 < result["metrics"]["recover_s"]["value"] < 0.6
    assert result["metrics"]["catchup_rounds"]["value"] >= 1
    assert result["metrics"]["outage_s"]["value"] == pytest.approx(1.2, abs=0.3)
    assert [ev["kind"] for ev in kept["executor"].fault_log] == [
        "kill", "restart", "kill", "restart"]
    after = {
        p: p.read_bytes()
        for p in toy_root.rglob("*")
        if p.is_file() and p.name != "BENCHMARK.json" and p in before
    }
    assert after == before


def test_a_window_that_compiles_says_what(harness):
    """My chip run, PR 36: one run of 25 met 6 compilations in its
    window and could not say which programs; the meter keeps each
    compilation's program name, and the ``[bench] window`` line gives
    those of the window."""
    import jax
    import jax.numpy as jnp

    from benchmarks.meters import CompileMeter

    meter = CompileMeter()

    def only_compiled_in_this_test(x):
        return x * 3 + 1

    jax.jit(only_compiled_in_this_test)(jnp.arange(7))
    assert meter.count == len(meter.names) >= 1
    assert any("only_compiled_in_this_test" in name for name in meter.names)


def test_the_mixed_pattern_rs_path_is_warmed_by_the_sizes_the_file_lists():
    """What the one window in 25 compiled (PERF.md section 7): a hub
    wave whose matrices were gathered from two sets of senders takes
    the RS column's three-step path, and every batch size of it is six
    programs.  ``warm_shapes.rs_mixed`` runs that path at each listed
    size through the program's own entry point, so a second meeting
    compiles nothing."""
    import numpy as np

    from benchmarks import executors, spec
    from benchmarks.meters import CompileMeter
    from cleisthenes_tpu.ops.backend import BatchCrypto

    cell = spec.load_cell(CELL)
    listed = cell.config["warm_shapes"]["rs_mixed"]
    assert listed == [[b, 2944] for b in range(17, 33)]
    crypto = BatchCrypto("tpu", 16, 5, 6)
    # every listed size crosses the device floor of the three-step path
    assert all(b * crypto.k * length >= 4 * crypto.erasure.HOST_FLOOR_BYTES
               for b, length in listed)
    meter = CompileMeter()
    executors.warm_shapes(crypto, None, {"rs_mixed": [[17, 2944]]})
    first = list(meter.names)
    assert any("_decode_kernel" in name for name in first)
    assert any("_encode_kernel" in name for name in first)
    # the wave the hub forms then: 17 matrices, two erasure patterns
    indices = np.tile(np.arange(6), (17, 1))
    indices[3] = [0, 1, 2, 3, 4, 9]
    shards = np.ones((17, 6, 2944), dtype=np.uint8)
    data, roots, dispatches = crypto.decode_recheck_batch(indices, shards)
    assert dispatches == 3 and data.shape == (17, 6, 2944)
    assert meter.names == first
