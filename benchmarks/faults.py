"""The timed path, broken underneath: what ``correct`` has to catch.

Used by benchmarks/tests and benchmarks/control.py only; the
benchmark's own runs never import this file.  Each fault takes the
warmed-up executor (benchmarks/executors.py) and breaks one guarantee
the configuration's file states, in the program's own objects, so that
the window drives the broken path.  They are the contract's faults as
far as these cells can have them (one process, one chip: there is no
exchange between chips to leave out):

  state_unchanged   a step returns its state unchanged
  half_batch        half of the batch is left out
  answer_altered    an answer is altered where it is produced
  coin_flipped      (lockstep) the common coin's bit is inverted: the
                    control for the BBA + coin layer, whose outcome
                    the committed batch alone does not show

and, where the configuration has a write-ahead log (``for_cell``), its
durability broken underneath one validator:

  wal_skipped       every second batch record is not written
  wal_unflushed     records reach the file two appends late, from a
                    buffer of the process's own: the later flush,
                    which only the prefix a crash would leave can show.
                    (Leaving out ``flush()`` alone loses nothing at
                    these sizes: Python's buffered writer hands a
                    record larger than its 8 KiB buffer straight to
                    the operating system; PERF.md, PR 33.)
  wal_altered       one transaction's last bit is flipped before the
                    record is framed, so that its CRC holds
  wal_behind        the appends are taken off the commit path: records
                    wait in a buffer of the process's own and reach the
                    file when the validator is next idle (its next
                    ``start_epoch``, and before the harness's last look
                    at the files).  After the drain the logs hold every
                    record, whole and in order; only the prefix held
                    when each epoch was stamped settled shows that the
                    settle was acknowledged before it was durable

and, where the cell's traffic has a fault schedule, the outage itself
broken (the first validator the schedule kills is the victim):

  resubmit_left_out  the clients' resubmission at the kill is skipped:
                     what only the killed validators had admitted and
                     not yet seen settled is gone (``lost``)
  restart_behind     the victim's log is cut back by its last whole
                     batch records before it restarts, and the new
                     process never asks its peers for what it missed:
                     it stays behind for good (``forked``)
  kill_not_taken     the victim is left connected: its peers and its
                     clients go on reaching it and it goes on proposing
                     while the books say it is down (``killed_proposed``)
"""

from __future__ import annotations

import collections
import copy
from typing import Callable, Dict


def _flip(tx: bytes) -> bytes:
    return tx[:-1] + bytes([tx[-1] ^ 1])


# -- served -------------------------------------------------------------------


class _Ledger(list):
    """A validator's committed_batches with its commit step broken."""

    def __init__(self, items, mode: str) -> None:
        super().__init__(items)
        self._mode = mode
        self._seen = 0

    def append(self, batch) -> None:
        self._seen += 1
        if self._mode == "state_unchanged" and self._seen % 2 == 0:
            return  # the commit returns its state unchanged
        if self._mode == "answer_altered":
            # this validator must not share the batch with the others
            batch = copy.deepcopy(batch)
            for txs in batch.contributions.values():
                if txs:
                    txs[0] = _flip(txs[0])
                    break
        super().append(batch)


def _served_ledger(mode: str) -> Callable:
    def fault(executor) -> None:
        hb = executor._nodes[1]
        hb.committed_batches = _Ledger(hb.committed_batches, mode)

    return fault


def _served_half_batch(executor) -> None:
    for hb in executor._nodes:
        orig = hb._create_batch

        def half(orig=orig):
            txs = orig()
            return txs[: len(txs) // 2]

        hb._create_batch = half


# -- served, with a write-ahead log -------------------------------------------


def _wal_skipped(executor) -> None:
    log = executor._nodes[1].batch_log
    orig = log.append
    state = {"calls": 0}

    def append(epoch, batch):
        state["calls"] += 1
        if state["calls"] % 2:
            orig(epoch, batch)

    log.append = append


def _wal_unflushed(executor) -> None:
    log = executor._nodes[1].batch_log
    orig = log._append_record_locked
    behind = collections.deque()

    def append_record(rec):
        # the last two records of a drained run hold its last epoch's
        # batch record, with or without a checkpoint after it
        behind.append(rec)
        if len(behind) > 2:
            orig(behind.popleft())

    log._append_record_locked = append_record


def _wal_altered(executor) -> None:
    log = executor._nodes[1].batch_log
    orig = log.append

    def append(epoch, batch):
        # the ledger keeps the batch as it was settled
        batch = copy.deepcopy(batch)
        for txs in batch.contributions.values():
            if txs:
                txs[0] = _flip(txs[0])
                break
        orig(epoch, batch)

    log.append = append


def _wal_behind(executor) -> None:
    hb = executor._nodes[1]
    log = hb.batch_log
    write = log._append_record_locked
    behind = collections.deque()
    log._append_record_locked = behind.append

    def catch_up() -> None:
        while behind:
            write(behind.popleft())

    start_epoch, observe = hb.start_epoch, executor.observe

    def idle_then_start():
        catch_up()
        return start_epoch()

    def idle_then_observe():
        catch_up()
        return observe()

    hb.start_epoch = idle_then_start
    executor.observe = idle_then_observe


# -- served, with a fault schedule --------------------------------------------

CUT_BACK_BATCHES = 2  # batch records taken off the victim's log


def _victim(executor) -> int:
    return executor.faults.events[0].members[0]


def _resubmit_left_out(executor) -> None:
    executor._resubmit = lambda: 0


def _restart_behind(executor) -> None:
    from benchmarks import reference

    cluster = executor.cluster
    victim = executor.ids[_victim(executor)]
    restart_node = cluster.restart_node

    def restart_behind(nid):
        if nid == victim:
            path = executor.wal._log(nid)
            with open(path, "rb") as fh:
                records, _end = reference.wal_records(fh.read())
            batches = [i for i, rec in enumerate(records)
                       if rec[0] == reference.WAL_BATCH]
            keep = batches[-CUT_BACK_BATCHES] if len(
                batches) >= CUT_BACK_BATCHES else 0
            cluster.nodes[nid].batch_log.close()
            with open(path, "r+b") as fh:
                # ... and whatever precedes that batch record's epoch
                # in the file stays: a whole prefix, as a crash leaves
                fh.truncate(records[keep - 1][3] if keep else 0)
        hb = restart_node(nid)
        if nid == victim:
            hb.request_catchup = lambda: None
            hb._request_catchup = lambda force=False: None
        return hb

    cluster.restart_node = restart_behind


def _kill_not_taken(executor) -> None:
    victim = _victim(executor)
    cluster = executor.cluster
    crash, reach = cluster.crash, executor._clients_reach
    changed = executor._membership_changed

    def crash_but_one(nid):
        if nid != executor.ids[victim]:
            crash(nid)

    def membership_changed():
        changed()
        if victim not in executor._up:
            # the process lives: it is driven as the others are
            executor._up_nodes = executor._up_nodes + [executor._nodes[victim]]

    cluster.crash = crash_but_one
    executor._clients_reach = lambda i, back: i == victim or reach(i, back)
    executor._membership_changed = membership_changed


OUTAGE_FAULTS: Dict[str, Callable] = {
    "resubmit_left_out": _resubmit_left_out,
    "restart_behind": _restart_behind,
    "kill_not_taken": _kill_not_taken,
}


WAL_FAULTS: Dict[str, Callable] = {
    "wal_skipped": _wal_skipped,
    "wal_unflushed": _wal_unflushed,
    "wal_altered": _wal_altered,
    "wal_behind": _wal_behind,
}


# -- lockstep -----------------------------------------------------------------


def _lockstep_state_unchanged(executor) -> None:
    cluster = executor.cluster
    stats = dict(cluster.last_stats)
    cluster.run_epoch = lambda: dict(stats)


def _lockstep_half_batch(executor) -> None:
    cluster = executor.cluster
    keep = set(cluster.ids[::2])
    orig = cluster.submit

    def submit(tx, node_id=None):
        if node_id in keep:
            orig(tx, node_id)

    cluster.submit = submit


def _lockstep_answer_altered(executor) -> None:
    tpke = executor.cluster.tpke
    orig = tpke.combine
    state = {"calls": 0}

    def combine(ct, shares):
        plain = orig(ct, shares)
        state["calls"] += 1
        if state["calls"] % 97 == 1:
            plain = _flip(plain)
        return plain

    tpke.combine = combine


def _lockstep_coin_flipped(executor) -> None:
    coin = executor.cluster.coin
    orig = coin.toss
    coin.toss = lambda coin_id, shares: not orig(coin_id, shares)


FAULTS: Dict[str, Dict[str, Callable]] = {
    "served": {
        "state_unchanged": _served_ledger("state_unchanged"),
        "half_batch": _served_half_batch,
        "answer_altered": _served_ledger("answer_altered"),
    },
    "lockstep": {
        "state_unchanged": _lockstep_state_unchanged,
        "half_batch": _lockstep_half_batch,
        "answer_altered": _lockstep_answer_altered,
        "coin_flipped": _lockstep_coin_flipped,
    },
}


def for_cell(cell) -> Dict[str, Callable]:
    """The faults this cell can have: its executor's, the log's where
    its configuration has one, and the outage's where its traffic has a
    fault schedule (``restart_behind`` where that restarts somebody);
    elsewhere they break nothing."""
    faults = dict(FAULTS[cell.config["executor"]])
    if cell.config.get("cluster", {}).get("wal_dir") is not None:
        faults.update(WAL_FAULTS)
    schedule = cell.traffic.get("faults")
    if schedule:
        faults.update(OUTAGE_FAULTS)
        if not any("restart" in row for row in schedule):
            del faults["restart_behind"]
    return faults


__all__ = ["FAULTS", "WAL_FAULTS", "OUTAGE_FAULTS", "for_cell"]
