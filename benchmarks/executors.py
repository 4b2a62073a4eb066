"""The two ways the harness drives the system under test.

``served``    SimulatedCluster through ``cluster.ingress(node)`` (the
              in-process twin of the client gRPC surface, i.e.
              ``IngressPlane.submit_frame``), driven round by round as
              tools/loadgen.py::run_arm's ``one_round`` drives it: every
              node's ``start_epoch()``, then ``net.step()`` /
              ``net.idle_phase()`` to quiescence.  Loops: ``open`` (due
              times on the wall clock) and ``backlog`` (held backlog).
``lockstep``  LockstepCluster: submit one epoch's transactions,
              ``run_epoch()``, repeat.  Loop: ``epoch``.

From the program these take only the system under test and its
counters.  Clocks, stamps, spans and what is handed to the reference
are the harness's own.  A configuration's file picks the executor by
name; a traffic file picks the loop.

A served configuration whose ``cluster.wal_dir`` is not null runs with
its validators' write-ahead logs on (``LogDir``): a fresh directory a
run under the checkout, what a crash would leave of each log handed to
the reference, the directory removed on every way out.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import shutil
import time
from typing import Callable, Dict, List, Optional, Sequence

from benchmarks.meters import SyncMeter
from benchmarks.spec import SpecError
from benchmarks.traffic import KIND_WARM, Arrival, TxSource

DRAIN_LIMIT_S = 60.0  # an answer may come a minute late, not never
# ... or, where rounds are long, this many of the longest round: what a
# drain has to settle is the held backlog (``backlog_batches``, 2) and
# the ``pipeline_depth`` (2) epochs in flight, and two rounds to spare
DRAIN_ROUNDS = 6
# warm-up rounds of the served path, as shares of a full batch: full
# ones, then a ramp down, since the device's RS decode compiles one
# program per shard length and only nearly full batches reach it.  A
# configuration's file may give its own as ``warm_up_fills``
WARMUP_FILLS = (1.0, 1.0, 0.97, 0.94, 0.91)
LOCKSTEP_WARMUP_MAX = 12
LOCKSTEP_WARMUP_CLEAN = 2
COMB_FILLER = (8, 512)  # groups, exponents a group


def _no_tick(_now: float) -> None:
    return None


def drain_limit_s(longest_round_s: float) -> float:
    """How long a drain may take: DRAIN_LIMIT_S, or DRAIN_ROUNDS of the
    longest round where that is more.  Rounds are never cut, so the
    limit only decides whether another one starts."""
    return max(DRAIN_LIMIT_S, DRAIN_ROUNDS * longest_round_s)


class RoundClock:
    """The longest of the loop's iterations (served round, lockstep
    epoch) timed so far, warm-up's and the window's; 0.0 before the
    first.  One that met a compilation is left out: it says how long
    the compiler took, not how long a round is."""

    def __init__(self, meter) -> None:
        self._meter = meter
        self.longest_s = 0.0

    @contextlib.contextmanager
    def timed(self):
        compiles = self._meter.count
        t0 = time.perf_counter()
        yield
        if self._meter.count == compiles:
            self.longest_s = max(self.longest_s, time.perf_counter() - t0)


def warm_shapes(crypto, group, shapes: Dict) -> None:
    """Run each exponentiation program the cell can meet once, at the
    sizes the configuration's file lists (``warm_shapes``: the share
    and coin waves' sizes move with the BBA round count, each size
    bucket is a program, and the rare ones would otherwise be met
    first inside a window).  Through the engine's own entry points."""
    from cleisthenes_tpu.ops import modmath

    eng = modmath.get_engine(crypto.engine_backend, crypto.mesh, group)
    # a grouped call is split by group size, so a small shape rides
    # with a filler of COMB_FILLER that lifts the call over the
    # comb's host floor
    filler = [(group.g, [3] * COMB_FILLER[1])] * COMB_FILLER[0]
    for groups, exps in shapes.get("comb", ()):
        call = [(group.g, [3] * exps)] * groups
        if exps != COMB_FILLER[1]:
            call = call + filler
        eng.pow_batch_grouped(call)
    for rows in shapes.get("dual_pow", ()):
        eng.dual_pow_batch(
            [group.g] * rows, [3] * rows, [group.g] * rows, [5] * rows
        )


class Spans:
    """Names the host's phases on the profiler's clock while a trace is
    on (jax.profiler.TraceAnnotation), and is free while it is off."""

    def __init__(self) -> None:
        self.on = False

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)


def _config(cell_config: Dict, seed: Optional[int]):
    from cleisthenes_tpu.config import Config

    fields = dict(cell_config["config"])
    if fields.get("mesh_shape") is not None:
        fields["mesh_shape"] = tuple(fields["mesh_shape"])
    if seed is not None:
        fields["seed"] = seed
    return Config(**fields)


def _process_gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    except PermissionError:
        pass  # it runs, as somebody else
    return False


def fresh_log_dir(parent: pathlib.Path, cell: str, seed: int) -> pathlib.Path:
    """``parent/<cell>.<seed>.<pid>``, made empty.  A log that finds a
    file recovers from it, so a run that met the last run's logs would
    start at that run's last epoch with a ledger of transactions nobody
    submitted.  What runs whose process is gone left here (a killed run
    removes nothing) goes on the way."""
    parent.mkdir(parents=True, exist_ok=True)
    for old in parent.iterdir():
        pid = old.name.rpartition(".")[2]
        if pid.isdigit() and _process_gone(int(pid)):
            shutil.rmtree(old, ignore_errors=True)
    mine = parent / f"{cell}.{seed}.{os.getpid()}"
    shutil.rmtree(mine, ignore_errors=True)
    mine.mkdir()
    return mine


class LogDir:
    """One run's write-ahead logs, and what a crash would leave of them
    at the durability the configuration's file states:

    ``durable_after: "flush"``  the file's size as a second look sees
        it while the validator's own handle is open: what the operating
        system holds, which is what a killed process leaves;
    ``durable_after: "fsync"``  its size at its last ``os.fsync`` /
        ``os.fdatasync`` (meters.SyncMeter): what a loss of power
        leaves.
    """

    LEVELS = ("flush", "fsync")

    def __init__(self, cell, seed: int) -> None:
        cfg = cell.config
        self.level = cfg.get("durable_after")
        if self.level not in self.LEVELS:
            raise SpecError(
                f"a configuration with a wal_dir states durable_after as one "
                f"of {self.LEVELS}, not {self.level!r}"
            )
        self.replicas = int(cfg["durable_replicas"])
        self._parent = (cell.root / cfg["cluster"]["wal_dir"]).resolve()
        if not self._parent.is_relative_to(cell.root.resolve()):
            raise SpecError(f"wal_dir {self._parent} leaves the checkout")
        self.path = fresh_log_dir(self._parent, cell.name, seed)
        self.syncs = SyncMeter()
        self.syncs.install()

    def _log(self, node_id: str) -> str:
        # SimulatedCluster._make_wal's naming
        return str(self.path / f"{node_id}.log")

    def written_bytes(self, ids: Sequence[str]) -> int:
        return sum(os.stat(self._log(nid)).st_size for nid in ids)

    def held_bytes(self, ids: Sequence[str]) -> List[int]:
        """Of each log, the prefix a crash at this moment would leave."""
        if self.level == "fsync":
            return [self.syncs.synced_bytes(self._log(nid)) for nid in ids]
        return [os.stat(self._log(nid)).st_size for nid in ids]

    def observe(self, ids: Sequence[str],
                held_at_settle: Sequence[Sequence[int]]) -> Dict:
        """Plain data for the reference; taken before the cluster is
        stopped, since a graceful close flushes and a kill does not.
        ``held_at_settle[e]`` is ``held_bytes`` as it read when the
        harness stamped epoch e settled: what an acknowledged settle
        could count on, whatever reached the file afterwards."""
        logs = {}
        for i, (nid, held) in enumerate(zip(ids, self.held_bytes(ids))):
            logs[nid] = {
                "path": self._log(nid),
                "held_bytes": held,
                "held_at_settle": [row[i] for row in held_at_settle],
            }
        return {
            "durable_after": self.level,
            "durable_replicas": self.replicas,
            "syncs": self.syncs.count,
            "logs": logs,
        }

    def remove(self) -> None:
        self.syncs.remove()
        shutil.rmtree(self.path, ignore_errors=True)
        with contextlib.suppress(OSError):
            self._parent.rmdir()  # unless another run's logs are there


class Served:
    """SimulatedCluster behind its ingress planes."""

    kind = "served"

    def __init__(self, cell, seed: int, spans: Spans, meter) -> None:
        from cleisthenes_tpu.protocol.cluster import SimulatedCluster
        from cleisthenes_tpu.transport.message import IngressStatus

        self.cell = cell
        self.seed = seed
        self.spans = spans
        self.meter = meter
        self.cfg = _config(cell.config, seed)
        # the schedule's seed and the keys' come from --seed, unless the
        # configuration's file fixes one (``cluster.key_seed``)
        kwargs = {"seed": seed, "key_seed": seed}
        kwargs.update(cell.config.get("cluster", {}))
        self.cluster = None
        self.wal: Optional[LogDir] = None
        if kwargs.get("wal_dir") is not None:
            self.wal = LogDir(cell, seed)
            kwargs["wal_dir"] = str(self.wal.path)
        try:
            self.cluster = SimulatedCluster(
                config=self.cfg, auto_propose=False, **kwargs
            )
        except BaseException:
            self.close()
            raise
        self.ids: List[str] = list(self.cluster.ids)
        self._nodes = [self.cluster.nodes[nid] for nid in self.ids]
        self._ingress = [self.cluster.ingress(nid) for nid in self.ids]
        self._ok = int(IngressStatus.OK)
        self.t_ordered: List[float] = []
        self.t_settled: List[float] = []
        # (tx, node_id, ok) of every submission, warm-up and drain too
        self.submissions: List[tuple] = []
        # of the window's submissions: when due, how late, how long
        self.due: List[float] = []
        self.late: List[float] = []
        self.submit_s: List[float] = []
        self.timed: List[bytes] = []
        self.timed_ok: List[bool] = []
        self.rounds = 0
        # (start, end, bytes of log written so far) of every round
        self.round_log: List[tuple] = []
        # per settled epoch, what a crash would have left of each log
        # at the moment the epoch was stamped settled
        self.held_at_settle: List[List[int]] = []
        self.clock = RoundClock(meter)

    # -- driving -------------------------------------------------------

    def _frontiers(self) -> tuple:
        ordered = min(hb.merged_ordered_frontier for hb in self._nodes)
        settled = min(hb.merged_settled_frontier for hb in self._nodes)
        return ordered, settled

    def _stamp(self) -> None:
        """An epoch is ordered, or settled, once EVERY validator's
        frontier has crossed it."""
        ordered, settled = self._frontiers()
        now = time.perf_counter()
        while len(self.t_ordered) < ordered:
            self.t_ordered.append(now)
        if len(self.t_settled) < settled:
            held = [] if self.wal is None else self.wal.held_bytes(self.ids)
            while len(self.t_settled) < settled:
                self.t_settled.append(now)
                self.held_at_settle.append(held)

    def _submit(self, a: Arrival, due: Optional[float]) -> None:
        node = a.nonce % len(self.ids)
        t1 = time.perf_counter()
        ack = self._ingress[node].submit(a.client, a.nonce, a.fee, a.tx)
        t2 = time.perf_counter()
        ok = int(ack.status) == self._ok
        self.submissions.append((a.tx, self.ids[node], ok))
        if due is not None:
            self.due.append(due)
            self.late.append(t1 - due)
            self.submit_s.append(t2 - t1)
            self.timed.append(a.tx)
            self.timed_ok.append(ok)

    def _round(self, between: Callable[[], None]) -> None:
        spans = self.spans
        net = self.cluster.net
        start = time.perf_counter()
        with self.clock.timed():
            with spans("start_epoch"):
                for hb in self._nodes:
                    hb.start_epoch()
            while True:
                with spans("step"):
                    stepped = net.step()
                if not stepped:
                    # the manual-driving contract (ChannelNetwork.step):
                    # a drained queue needs the idle phase, and another
                    # pass if that produced traffic
                    with spans("idle_phase"):
                        net.idle_phase()
                self._stamp()
                between()
                if not stepped and net.pending_count() == 0:
                    break
        self.rounds += 1
        self.round_log.append((
            start, time.perf_counter(),
            None if self.wal is None else self.wal.written_bytes(self.ids),
        ))

    def _quiet(self) -> bool:
        ordered, settled = self._frontiers()
        return self.cluster.pending() == 0 and ordered == settled

    def _drain(self) -> None:
        start = time.perf_counter()
        while not self._quiet() and (
            time.perf_counter() - start < drain_limit_s(self.clock.longest_s)
        ):
            self._round(lambda: None)

    def warm_up(self) -> None:
        """The listed shapes, if any, then full rounds."""
        hb0 = self._nodes[0]
        warm_shapes(hb0.crypto, hb0.tpke.group,
                    self.cell.config.get("warm_shapes", {}))
        source = TxSource(
            self.cell.traffic, self.cell.config["tx_bytes"], self.seed,
            kind=KIND_WARM,
        )
        for fill in self.cell.config.get("warm_up_fills", WARMUP_FILLS):
            for a in source.take(int(fill * self.cfg.batch_size)):
                self._submit(a, None)
            self._round(lambda: None)
        self._drain()

    # -- loops ---------------------------------------------------------

    def run_open(
        self,
        due: Sequence[float],
        arrivals: Sequence[Arrival],
        seconds: float,
        tick: Callable[[float], None] = _no_tick,
    ) -> Dict:
        """Open loop: submit whatever is due between delivery waves,
        never waiting for the service.  Returns the window's ends."""
        count = len(arrivals)
        nxt = 0
        spans = self.spans
        t0 = time.perf_counter()

        def pump() -> None:
            nonlocal nxt
            now = time.perf_counter() - t0
            if nxt < count and due[nxt] <= now:
                with spans("submit"):
                    while nxt < count and due[nxt] <= now:
                        self._submit(arrivals[nxt], t0 + due[nxt])
                        nxt += 1

        while True:
            pump()
            now = time.perf_counter() - t0
            if nxt >= count and now >= seconds:
                break
            tick(now)
            if self._quiet():
                # nothing to order: wait for the next arrival
                wake = due[nxt] if nxt < count else seconds
                with spans("wait_arrival"):
                    time.sleep(min(0.001, max(0.0, wake - now)))
                continue
            self._round(pump)
        t_end = time.perf_counter()
        with spans("drain"):
            self._drain()
        return {"t0": t0, "t_end": t_end}

    def run_backlog(
        self,
        seconds: float,
        tick: Callable[[float], None] = _no_tick,
        closed: Callable[[], None] = lambda: None,
    ) -> Dict:
        """Backlog held at ``backlog_batches`` full batches, topped up
        after every round.  The window closes with the round in which
        the first settle at or after ``seconds`` falls: rounds run to
        quiescence, so no epoch is in flight at either end.  ``closed``
        is called as it closes, before the drain."""
        source = TxSource(
            self.cell.traffic, self.cell.config["tx_bytes"], self.seed
        )
        target = int(self.cell.traffic["backlog_batches"]) * self.cfg.batch_size
        spans = self.spans
        t0 = time.perf_counter()
        first = len(self.t_settled)
        first_round = self.rounds
        while True:
            need = target - self.cluster.pending()
            if need > 0:
                with spans("submit"):
                    for a in source.take(need):
                        self._submit(a, t0)
            tick(time.perf_counter() - t0)
            self._round(lambda: None)
            if self.t_settled[first:] and self.t_settled[-1] - t0 >= seconds:
                break
        t_end = time.perf_counter()
        rounds = self.rounds - first_round
        closed()
        with spans("drain"):
            self._drain()
        return {"t0": t0, "t_end": t_end, "first_epoch": first,
                "rounds": rounds}

    # -- what the harness reads ------------------------------------------

    def counters(self) -> Dict:
        from cleisthenes_tpu.ops import placement

        hb0 = self._nodes[0]
        out = {
            "hub": dict(hb0.hub.stats()),
            "delivery": dict(self.cluster.net.delivery_stats()),
            "placement": placement.snapshot(),
            "ingress": dict(hb0.metrics.snapshot()["ingress"]),
            "compiles": self.meter.count,
            "epochs": len(self.t_settled),
            "rounds": self.rounds,
        }
        if self.wal is not None:
            out["wal_bytes"] = self.wal.written_bytes(self.ids)
        return out

    def observe(self) -> Dict:
        """Plain data for the reference: nothing of the program's
        objects but the transactions' bytes and the ledgers' shape."""
        return {
            "node_ids": list(self.ids),
            "submissions": self.submissions,
            "ledgers": {
                nid: [b.contributions for b in hb.merged_batches]
                for nid, hb in zip(self.ids, self._nodes)
            },
            "evicted": sum(hb.mempool.evicted for hb in self._nodes),
            "ordered": {
                nid: hb.merged_ordered_frontier
                for nid, hb in zip(self.ids, self._nodes)
            },
            "settled": {
                nid: hb.merged_settled_frontier
                for nid, hb in zip(self.ids, self._nodes)
            },
            "batch_size": max(self.cfg.batch_size, self.cfg.n),
            "wal": None if self.wal is None else self.wal.observe(
                self.ids, self.held_at_settle
            ),
        }

    def close(self) -> None:
        """Stops the cluster and takes the logs away; run.py calls it
        once the comparison has read them, and again on every other way
        out of a run."""
        if self.cluster is not None:
            self.cluster.stop()
            self.cluster = None
            self._nodes = []
            self._ingress = []
        if self.wal is not None:
            self.wal.remove()
            self.wal = None


class Lockstep:
    """LockstepCluster, one epoch at a time."""

    kind = "lockstep"

    def __init__(self, cell, seed: int, spans: Spans, meter) -> None:
        from cleisthenes_tpu.protocol.spmd import LockstepCluster

        self.cell = cell
        self.seed = seed
        self.spans = spans
        self.meter = meter
        self.cfg = _config(cell.config, None)
        self.cluster = LockstepCluster(
            config=self.cfg, key_seed=seed,
            **dict(cell.config.get("cluster", {})),
        )
        self.ids: List[str] = list(self.cluster.ids)
        n = self.cfg.n
        self.per_epoch = (max(self.cfg.batch_size, n) // n) * n
        self._source = TxSource(
            cell.traffic, cell.config["tx_bytes"], seed
        )
        self.epochs: List[Dict] = []  # one row per epoch, warm-up too
        self.clock = RoundClock(meter)

    def _epoch(self) -> Dict:
        n = len(self.ids)
        submitted: Dict[str, List[bytes]] = {nid: [] for nid in self.ids}
        with self.clock.timed():
            with self.spans("submit"):
                for j, a in enumerate(self._source.take(self.per_epoch)):
                    nid = self.ids[j % n]
                    self.cluster.submit(a.tx, nid)
                    submitted[nid].append(a.tx)
            before = len(self.cluster.committed_batches)
            with self.spans("run_epoch"):
                stats = dict(self.cluster.run_epoch())
        row = {
            "epoch": before,
            "submitted": submitted,
            "stats": stats,
            "t_end": time.perf_counter(),
        }
        self.epochs.append(row)
        return row

    def warm_up(self) -> None:
        """The listed shapes, then epochs until LOCKSTEP_WARMUP_CLEAN
        in a row compile nothing."""
        warm_shapes(self.cluster.crypto, self.cluster.tpke.group,
                    self.cell.config.get("warm_shapes", {}))
        clean = 0
        for _ in range(LOCKSTEP_WARMUP_MAX):
            before = self.meter.count
            self._epoch()
            clean = clean + 1 if self.meter.count == before else 0
            if clean >= LOCKSTEP_WARMUP_CLEAN:
                break

    def run_epochs(
        self,
        seconds: float,
        tick: Callable[[float], None] = _no_tick,
    ) -> Dict:
        """Closed loop; the window closes at the first epoch boundary
        at or after ``seconds``."""
        first = len(self.epochs)
        t0 = time.perf_counter()
        while True:
            tick(time.perf_counter() - t0)
            row = self._epoch()
            if row["t_end"] - t0 >= seconds:
                break
        return {"t0": t0, "t_end": self.epochs[-1]["t_end"],
                "first_epoch": first}

    def counters(self) -> Dict:
        from cleisthenes_tpu.ops import placement

        return {
            "placement": placement.snapshot(),
            "compiles": self.meter.count,
            "epochs": len(self.epochs),
        }

    def observe(self, first_epoch: int = 0) -> Dict:
        committed = self.cluster.committed_batches
        rows = []
        for row in self.epochs[first_epoch:]:
            i = row["epoch"]
            rows.append({
                "epoch": row["epoch"],
                "submitted": row["submitted"],
                "committed": (
                    committed[i].contributions if i < len(committed) else None
                ),
                "bba_rounds": int(row["stats"].get("bba_rounds", -1)),
            })
        keys = self.cluster.keys
        pub = keys[self.ids[0]].coin_pub
        group = pub.group
        return {
            "node_ids": list(self.ids),
            "epochs": rows,
            "coin": {
                "group": {"p": group.p, "q": group.q, "g": group.g},
                "threshold": pub.threshold,
                "shares": [
                    (keys[nid].coin_share.index, keys[nid].coin_share.value)
                    for nid in self.ids[: pub.threshold]
                ],
                "master_pub": pub.master,
            },
        }

    def close(self) -> None:
        self.cluster = None


EXECUTORS = {"served": Served, "lockstep": Lockstep}

__all__ = ["Served", "Lockstep", "LogDir", "Spans", "EXECUTORS", "RoundClock",
           "drain_limit_s", "fresh_log_dir"]
