"""PR 33's logged configuration, ``n16-b4k-wal``, and its cell: the
files load by name, a toy-size copy runs and leaves no log behind, the
reference reads what the program's writer writes and counts what a
hand-broken log lacks, ``SyncMeter`` reads what a sync left, and the
log's four faults read not correct.  (A new file beside
test_harness.py, whose fixtures and helpers it borrows.)"""

import copy
import json
import os
import struct
import subprocess
import sys
import zlib

import pytest

from benchmarks import reference
from benchmarks.tests.test_harness import (  # noqa: F401
    add_cell,
    add_config,
    harness,
    toy_root,
)

CELL = "toy-wal.steady"
WAL_NUMBERS = ["wal_short", "wal_missing", "wal_late", "wal_wrong",
               "wal_unordered", "wal_torn"]


@pytest.fixture
def wal_root(toy_root):
    """The toy checkout with a four-validator copy of the logged
    configuration and of its cell; a checkpoint every 4 commits, so
    that a two-second window meets the record kind that is skipped."""
    add_config(toy_root, "toy-wal", "n16-b4k-wal",
               {"n": 4, "batch_size": 64, "ledger_checkpoint_every": 4})
    path = toy_root / "benchmarks/configs/toy-wal.json"
    cfg = json.loads(path.read_text())
    cfg["durable_replicas"] = 4
    path.write_text(json.dumps(cfg))
    add_cell(toy_root, CELL, "n16-b4k-wal.steady", "toy-wal",
             {"rate_tx_per_s": 300})
    return toy_root


def test_the_configuration_and_its_cell_are_the_twins_with_the_log():
    from benchmarks import faults, spec

    cell = spec.load_cell("n16-b4k-wal.steady")
    twin = spec.load_cell("n16-b4k.steady")
    assert cell.chips == 1
    mine = {"ledger_fsync": False, "ledger_checkpoint_every": 32}
    assert cell.config["config"] == dict(twin.config["config"], **mine)
    # ... and the keys dealt once: the coin's luck decides which rounds
    # run long, and the rounds after a checkpoint decide the tail
    assert cell.config["cluster"] == dict(
        twin.config["cluster"], wal_dir=".bench_wal", key_seed=2148100002
    )
    assert cell.config["tx_bytes"] == twin.config["tx_bytes"]
    assert cell.config["guarantees"][:-2] == twin.config["guarantees"]
    assert (cell.config["durable_after"],
            cell.config["durable_replicas"]) == ("flush", 16)
    assert list(cell.config["reduced"]) == ["wal_disks"]
    shape = [k for k in twin.traffic if k not in ("who", "why")]
    assert {k: cell.traffic[k] for k in shape} == {
        k: twin.traffic[k] for k in shape
    }
    # the twin's metrics, and the tail that lies inside the share of
    # transactions that wait behind a checkpoint round
    assert [m["name"] for m in cell.end_to_end] == [
        "settle_p50_ms", "settle_p90_ms", "settle_p99_ms", "order_p50_ms",
        "setup_s",
    ]
    layer = {"wal_pct", "wal_ms_per_commit", "wal_bytes_per_epoch",
             "wal_checkpoint_stall_ms"}
    assert {m["name"] for m in cell.per_layer} == layer | {
        m["name"] for m in twin.per_layer
    }
    for name in layer:
        spec.load_reader(name)
    # the control breaks the log only where there is one
    assert sorted(faults.for_cell(cell)) == sorted(
        list(faults.FAULTS["served"]) + list(faults.WAL_FAULTS)
    )
    assert faults.for_cell(twin) == faults.FAULTS["served"]
    lockstep = spec.load_cell("n128-b10k-lockstep.saturated")
    assert faults.for_cell(lockstep) == faults.FAULTS["lockstep"]


@pytest.mark.parametrize("trace", [False, True])
def test_a_toy_copy_runs_twice_in_one_checkout_and_keeps_no_log(
    harness, wal_root, trace
):
    """The second run would recover from the first one's logs, and read
    ``foreign`` > 0, had it been given the same directory."""
    for seed in (2**31 + 5, 2**31 + 5):
        result = harness.run_cell(CELL, seed, 2.0, trace, root=wal_root)
        compared = result["compared"]
        assert result["correct"] is True, compared
        assert [k for k in compared if k.startswith("wal_")] == WAL_NUMBERS
        assert all(compared[k] == {"value": 0, "limit": 0}
                   for k in WAL_NUMBERS)
        assert result["attempted"] > 0 and result["failed"] == 0
        assert not (wal_root / ".bench_wal").exists()
    if trace:
        metrics = result["metrics"]
        assert 0 < metrics["wal_pct"]["value"] < 100
        assert metrics["wal_ms_per_commit"]["value"] > 0
        # an epoch writes its transactions twice over (COrd, CLOG) at
        # four validators: more than a record's framing, less than 1 MB
        assert 1e3 < metrics["wal_bytes_per_epoch"]["value"] < 1e6
        # a checkpoint every 4 commits: some round of the window has one
        assert "wal_checkpoint_stall_ms" in metrics
        assert metrics["gen_late_p95_ms"]["value"] > 0
        assert metrics["compiles_in_window.lat"]["value"] == 0
    else:
        assert sorted(result["metrics"]) == [
            "order_p50_ms", "settle_p50_ms", "settle_p90_ms",
            "settle_p99_ms", "setup_s",
        ]
        p50, p90, p99 = (result["metrics"][k]["value"] for k in (
            "settle_p50_ms", "settle_p90_ms", "settle_p99_ms"))
        assert p99 >= p90 >= p50 > 0
    json.dumps(result)


def test_a_cell_without_a_log_keeps_its_keys(harness, toy_root):
    result = harness.run_cell(
        "toy-served.steady", 2**31 + 5, 1.5, True, root=toy_root
    )
    assert result["correct"] is True
    assert not [k for k in result["compared"] if k.startswith("wal_")]
    assert not [k for k in result["metrics"] if k.startswith("wal_")]
    assert not (toy_root / ".bench_wal").exists()


def test_the_logs_go_when_the_window_raises(harness, wal_root):
    seen = {}

    def fault(executor):
        seen["dir"] = executor.wal.path
        assert len(list(seen["dir"].glob("*.log"))) == 4

        def boom():
            raise RuntimeError("the window broke")

        executor.cluster.net.step = boom

    with pytest.raises(RuntimeError, match="the window broke"):
        harness.run_cell(CELL, 7, 1.5, False, root=wal_root, fault=fault)
    assert not seen["dir"].exists()
    assert not (wal_root / ".bench_wal").exists()


def test_what_a_killed_run_left_goes_and_a_live_runs_stays(tmp_path):
    from benchmarks.executors import fresh_log_dir

    child = subprocess.Popen([sys.executable, "-c", "pass"])
    child.wait(timeout=30)
    parent = tmp_path / ".bench_wal"
    killed = parent / f"some.cell.3.{child.pid}"
    live = parent / f"some.cell.3.{os.getppid()}"
    for old in (killed, live):
        old.mkdir(parents=True)
        (old / "node000.log").write_bytes(b"CLOG")
    mine = fresh_log_dir(parent, "some.cell", 4)
    (mine / "node000.log").write_bytes(b"CLOG")
    assert not killed.exists() and live.exists()
    assert fresh_log_dir(parent, "some.cell", 4) == mine
    assert list(mine.iterdir()) == []


# -- the reference reads what the program writes ----------------------------


def _frame(magic, body):
    return (magic + struct.pack(">I", len(body)) + body
            + struct.pack(">I", zlib.crc32(body)))


def test_the_reference_reads_what_the_programs_replay_reads(tmp_path):
    from cleisthenes_tpu.core.batch import Batch
    from cleisthenes_tpu.core.ledger import BatchLog

    path = str(tmp_path / "node000.log")
    log = BatchLog(path)
    batches = [
        Batch(contributions={"node001": [b"a" * 64, b"b" * 64],
                             "node000": [b"c" * 64], "node002": []}),
        Batch(contributions={"node000": [b"\xc3\x28" * 32]}),
    ]
    for epoch, batch in enumerate(batches):
        log.append_ordered(epoch, {"node000": b"ct0" * epoch, "node001": b""})
        log.append(epoch, batch)
        log.append_checkpoint(epoch, [{b"a" * 64}, set()])
    log.lane_view(1).append(0, batches[1])
    log.lane_view(1).append_ordered(0, {"node000": b"x"})
    log.append_reconfig(1, 9, [("node000", "127.0.0.1", 1)], b"digest")
    log.append(2, batches[0])
    whole = os.stat(path).st_size
    with open(path, "rb") as fh:
        data = fh.read()
    records, end = reference.wal_records(data)
    assert end == whole == len(data)
    got = [(e, c) for m, e, c, _ in records if m == reference.WAL_BATCH]
    assert got == [(e, b.contributions) for e, b in log.replay()]
    assert len(got) == 3
    ordered = [e for m, e, _, _ in records if m == reference.WAL_ORDERED]
    assert ordered == [e for e, _ in log.replay_ordered()] == [0, 1]
    assert [end for _, _, _, end in records] == sorted(
        {end for _, _, _, end in records}
    ) and records[-1][3] == whole
    assert [m for m, _, _, _ in records] == [
        b"COrd", b"CLOG", b"CCKP", b"COrd", b"CLOG", b"CCKP",
        b"LCLG", b"LOrd", b"RCFG", b"CLOG",
    ]
    # a tail torn anywhere inside the last record: both stop before it
    log.close()
    for cut in (1, 7, 40, whole - end + 3):
        with open(path, "r+b") as fh:
            fh.truncate(whole - cut)
        with open(path, "rb") as fh:
            torn = fh.read()
        records, end = reference.wal_records(torn)
        assert len(torn) - end > 0
        assert len([1 for m, _, _, _ in records if m == b"CLOG"]) == 2
        reopened = BatchLog(path)  # recovery truncates the same tail away
        assert os.stat(path).st_size == end
        assert len(list(reopened.replay())) == 2
        reopened.close()
        with open(path, "wb") as fh:
            fh.write(data)
    # a whole record of a kind nobody knows, and one whose CRC is off
    assert reference.wal_records(data + _frame(b"XXXX", b"1"))[1] == whole
    off = bytearray(data)
    off[20] ^= 1
    assert reference.wal_records(bytes(off)) == ([], 0)


# -- each number, on logs broken by hand ------------------------------------


def _ordered_record(proposers, epoch, ciphertext=None):
    out = struct.pack(">QI", epoch, len(proposers))
    for nid in proposers:
        out += struct.pack(">I", len(nid)) + nid.encode()
        out += struct.pack(">I", 5) + (ciphertext or b"ct%03d" % epoch)
    return _frame(b"COrd", out)


def _logged_obs(tmp_path):
    """Three validators, three settled epochs, sound logs written by
    hand in the format's own words; ``cut(nid, data)`` rewrites one."""
    ids = ["node000", "node001", "node002"]
    txs = [bytes([i]) * 16 for i in range(18)]
    subs = [(tx, ids[i % 3], True) for i, tx in enumerate(txs)]
    ledger = [
        {nid: [t for t, home, _ in subs[lo:lo + 6] if home == nid]
         for nid in ids}
        for lo in (0, 6, 12)
    ]

    def batch_body(epoch):
        out = struct.pack(">QI", epoch, len(ids))
        for nid in ids:
            out += struct.pack(">I", len(nid)) + nid.encode()
            out += struct.pack(">I", len(ledger[epoch][nid]))
            for tx in ledger[epoch][nid]:
                out += struct.pack(">I", len(tx)) + tx
        return out

    records = []
    for epoch in range(3):
        records.append(_ordered_record(ids, epoch))
        records.append(_frame(b"CLOG", batch_body(epoch)))
        if epoch == 1:
            records.append(_frame(b"CCKP", b"skipped by its length"))
    # each epoch was stamped settled once its batch record was held
    stamped = [sum(map(len, records[:upto])) for upto in (2, 5, 7)]
    logs = {}
    for nid in ids:
        path = tmp_path / f"{nid}.log"
        path.write_bytes(b"".join(records))
        logs[nid] = {"path": str(path), "held_bytes": path.stat().st_size,
                     "held_at_settle": list(stamped)}
    obs = {
        "node_ids": ids,
        "submissions": subs,
        "ledgers": {nid: copy.deepcopy(ledger) for nid in ids},
        "wal": {"durable_after": "flush", "durable_replicas": 3,
                "syncs": 0, "logs": logs},
    }
    return obs, records


def _rewrite(obs, nid, records):
    log = obs["wal"]["logs"][nid]
    with open(log["path"], "wb") as fh:
        fh.write(b"".join(records))
    log["held_bytes"] = os.stat(log["path"]).st_size


def _break_record_removed(obs, records):
    # epoch 1's batch record is not in node001's log
    _rewrite(obs, "node001", records[:3] + records[4:])
    return {"wal_missing": 1, "wal_short": 6}


def _break_byte_flipped(obs, records):
    # one bit of a transaction in epoch 2's batch record: its CRC fails,
    # and it and what follows are a torn tail
    bad = bytearray(records[6])
    bad[-10] ^= 1
    _rewrite(obs, "node002", records[:6] + [bytes(bad)])
    return {"wal_missing": 1, "wal_short": 6, "wal_torn": len(bad)}


def _break_batch_before_ordered(obs, records):
    _rewrite(obs, "node000", [records[1], records[0]] + records[2:])
    return {"wal_unordered": 1}


def _break_torn_tail(obs, records):
    # sound records, then the head of one more that never finished
    _rewrite(obs, "node001", records + [records[1][:21]])
    return {"wal_torn": 21}


def _break_one_replica_short(obs, records):
    # node002 holds only what the operating system had: one epoch less
    obs["wal"]["logs"]["node002"]["held_bytes"] -= len(records[-1])
    return {"wal_missing": 1, "wal_short": 6}


def _break_altered_under_a_good_crc(obs, records):
    obs["ledgers"]["node001"][0]["node000"][0] = b"\xff" * 16
    return {"wal_wrong": 1}


def _break_epoch_never_settled(obs, records):
    obs["ledgers"]["node002"].pop()
    return {"wal_wrong": 1}


def _break_epoch_twice(obs, records):
    _rewrite(obs, "node000", records + [records[1]])
    return {"wal_wrong": 1}


def _break_written_behind(obs, records):
    # node001 acknowledged epoch 1 with its records still in a buffer of
    # its own, and caught up once idle: the file is whole at the end
    obs["wal"]["logs"]["node001"]["held_at_settle"][1] -= len(records[3])
    return {"wal_late": 1, "wal_short": 6}


def _break_ordered_record_behind(obs, records):
    # ... or only epoch 2's ordered record came after its settle, with
    # the batch record in its place before it
    log = obs["wal"]["logs"]["node002"]
    _rewrite(obs, "node002", records[:5] + [records[6], records[5]])
    log["held_at_settle"][2] -= len(records[5])
    return {"wal_late": 1, "wal_unordered": 1}


def _break_ordered_records_disagree(obs, records):
    # node002 logged another ciphertext for epoch 0 than the others did
    other = _ordered_record(obs["node_ids"], 0, b"CT000")
    _rewrite(obs, "node002", [other] + records[1:])
    return {"wal_wrong": 1}


def _break_a_proposer_nobody_ordered(obs, records):
    # epoch 0's ordered record names two proposers, its batch three
    ids = obs["node_ids"]
    fewer = _ordered_record(ids[:2], 0)
    for nid in ids:
        _rewrite(obs, nid, [fewer] + records[1:])
        log = obs["wal"]["logs"][nid]
        log["held_at_settle"] = [
            at - (len(records[0]) - len(fewer)) for at in log["held_at_settle"]
        ]
    return {"wal_wrong": 3}


@pytest.mark.parametrize("broken", [
    _break_record_removed, _break_byte_flipped, _break_batch_before_ordered,
    _break_torn_tail, _break_one_replica_short,
    _break_altered_under_a_good_crc, _break_epoch_never_settled,
    _break_epoch_twice, _break_written_behind, _break_ordered_record_behind,
    _break_ordered_records_disagree, _break_a_proposer_nobody_ordered,
], ids=lambda f: f.__name__[len("_break_"):])
def test_a_log_broken_by_hand_reads_its_number(tmp_path, broken):
    obs, records = _logged_obs(tmp_path)
    sound = reference.compare_wal(obs)
    assert list(sound) == WAL_NUMBERS
    assert reference.verdict(sound) and not any(v for v, _ in sound.values())
    want = dict.fromkeys(WAL_NUMBERS, 0)
    want.update(broken(obs, records))
    got = reference.compare_wal(obs)
    assert {k: v for k, (v, _limit) in got.items()} == want
    assert not reference.verdict(got)


def test_fewer_replicas_may_be_asked_for(tmp_path):
    obs, records = _logged_obs(tmp_path)
    _break_one_replica_short(obs, records)
    obs["wal"]["durable_replicas"] = 2
    got = reference.compare_wal(obs)
    assert got["wal_short"] == (0, 0) and got["wal_missing"] == (1, 0)


def test_an_epoch_with_no_stamp_is_judged_by_what_is_held_at_the_end(tmp_path):
    """A validator may have settled an epoch ahead of the slowest one,
    which the harness then never stamped."""
    obs, records = _logged_obs(tmp_path)
    for log in obs["wal"]["logs"].values():
        log["held_at_settle"].pop()
    assert not any(v for v, _ in reference.compare_wal(obs).values())


# -- what a sync left --------------------------------------------------------


def test_sync_meter_reads_what_the_last_sync_left(tmp_path):
    from benchmarks.meters import SyncMeter
    from cleisthenes_tpu.core.batch import Batch
    from cleisthenes_tpu.core.ledger import BatchLog

    fsync, fdatasync = os.fsync, os.fdatasync
    meter = SyncMeter()
    meter.install()
    try:
        synced = BatchLog(str(tmp_path / "synced.log"), fsync=True)
        flushed = BatchLog(str(tmp_path / "flushed.log"), fsync=False)
        batch = Batch(contributions={"node000": [b"t" * 64]})
        for epoch in range(3):
            synced.append(epoch, batch)
            flushed.append(epoch, batch)
        size = os.stat(synced.path).st_size
        assert size == os.stat(flushed.path).st_size > 0
        assert meter.count == 3
        assert meter.synced_bytes(synced.path) == size
        assert meter.synced_bytes(flushed.path) == 0
        # written and flushed after the last sync: not yet on the disk
        synced.fsync = False
        synced.append(3, batch)
        assert os.stat(synced.path).st_size > size
        assert meter.synced_bytes(synced.path) == size
        with open(flushed.path, "ab") as fh:
            os.fdatasync(fh)
        assert meter.count == 4
        assert meter.synced_bytes(flushed.path) == size
        synced.close()
        flushed.close()
    finally:
        meter.remove()
    assert (os.fsync, os.fdatasync) == (fsync, fdatasync)


def test_a_configuration_that_syncs_nothing_is_held_to_fsync(
    harness, wal_root
):
    """The next deployment's level, on a program that cannot give it
    yet (SimulatedCluster drops ``ledger_fsync``): nothing was synced,
    so nothing is held, and every settled batch is missing."""
    path = wal_root / "benchmarks/configs/toy-wal.json"
    cfg = json.loads(path.read_text())
    cfg["durable_after"] = "fsync"
    cfg["config"]["ledger_fsync"] = True
    path.write_text(json.dumps(cfg))
    result = harness.run_cell(CELL, 11, 1.5, False, root=wal_root)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False
    assert compared["wal_missing"] > 0 and compared["wal_missing"] % 4 == 0
    # warm-up's transactions are held to it too
    assert compared["wal_short"] > result["attempted"] > 0
    assert compared["wal_torn"] == 0


# -- the log broken underneath the timed path --------------------------------


@pytest.mark.parametrize("name,caught_by", [
    ("wal_skipped", {"wal_missing", "wal_short"}),
    ("wal_unflushed", {"wal_missing", "wal_short"}),
    ("wal_altered", {"wal_wrong", "wal_short"}),
    ("wal_behind", {"wal_late", "wal_short"}),
])
def test_a_fault_under_the_log_reads_not_correct(
    harness, wal_root, name, caught_by
):
    from benchmarks.faults import WAL_FAULTS

    result = harness.run_cell(
        CELL, 99, 1.5, False, root=wal_root, fault=WAL_FAULTS[name]
    )
    assert result["correct"] is False
    over = {
        k for k, v in result["compared"].items() if v["value"] > v["limit"]
    }
    assert caught_by <= over, result["compared"]
    if name == "wal_behind":
        # at rest the logs are whole: only the prefix held at each
        # settle's stamp tells this run from a sound one
        assert over == caught_by, result["compared"]
    # the ledgers agree: only the log's numbers see it
    assert all(k.startswith("wal_") for k in over), result["compared"]
    assert not (wal_root / ".bench_wal").exists()


def test_the_checkpoint_stall_is_read_from_the_rounds_that_hold_one():
    from benchmarks import spec

    read = spec.load_reader("wal_checkpoint_stall_ms")
    # warm-up's round, then a window of rounds of 0.5 s, but for the two
    # in which epochs 3 and 7 (a checkpoint every 4 commits) settled
    lengths = [9.0, 0.5, 0.5, 2.5, 0.5, 0.5, 0.5, 1.5, 0.5]
    rounds, t = [], 91.0
    for width in lengths:
        rounds.append((t, t + width, 1000))
        t += width
    made = {
        "config": {"config": {"ledger_checkpoint_every": 4}},
        "t0": 100.0, "t_end": t,
        "rounds": rounds,
        # epoch e settles at the end of round e
        "t_settled": [b for _a, b, _w in rounds],
    }
    assert read(made) == pytest.approx(1e3 * ((2.5 + 1.5) / 2 - 0.5))
    # no checkpoint in the window, none stated, or no rounds: nothing
    assert read(dict(made, t_settled=made["t_settled"][:3])) is None
    made["config"]["config"]["ledger_checkpoint_every"] = 0
    assert read(made) is None
    assert read({"t0": 0.0, "t_end": 1.0}) is None
