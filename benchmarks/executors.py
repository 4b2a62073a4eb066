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

A served cell whose traffic file holds ``faults`` (``fault_schedule``)
has validators killed and restarted inside its window, under the open
loop: ``Served`` applies each event at the first delivery-wave boundary
at or after its time, keeps who is up and who is in service, routes the
clients by the traffic file's model, and hands the reference what it
needs to hold the deployment to its guarantees across the outage.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
import shutil
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

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
# when the clients of a restarted validator go back to it: once it is in
# service again (a health-checked balancer), or once its port is open
CLIENTS_RETURN = ("in_service", "at_restart")


def _no_tick(_now: float) -> None:
    return None


def drain_limit_s(longest_round_s: float) -> float:
    """How long a drain may take: DRAIN_LIMIT_S, or DRAIN_ROUNDS of the
    longest round where that is more.  Rounds are never cut, so the
    limit only decides whether another one starts."""
    return max(DRAIN_LIMIT_S, DRAIN_ROUNDS * longest_round_s)


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    at: float  # share of the window
    kind: str  # "kill" or "restart"
    members: tuple  # indices into the roster's ids, ascending


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    events: tuple  # of FaultEvent, by time
    clients_return: str


def fault_schedule(traffic: Dict, ids: Sequence[str],
                   tolerated: int) -> Optional[FaultSchedule]:
    """The traffic file's ``faults`` (None where it has none): a list of
    ``{"at": <share of the window>, "kill" | "restart": [<ids>]}``,
    held to what a run can apply: the open loop, known validators, a
    restart only of one that is down, never more than ``tolerated`` (the
    roster's f) down at once."""
    if "faults" not in traffic:
        return None
    if traffic.get("loop") != "open":
        raise SpecError("a fault schedule needs the open loop: requests "
                        "have to come on a schedule while validators are down")
    clients_return = traffic.get("clients_return", CLIENTS_RETURN[0])
    if clients_return not in CLIENTS_RETURN:
        raise SpecError(f"clients_return is one of {CLIENTS_RETURN}, "
                        f"not {clients_return!r}")
    index = {nid: i for i, nid in enumerate(ids)}
    events, down, last = [], set(), 0.0
    for row in traffic["faults"]:
        row = row if isinstance(row, dict) else {}
        kinds = [k for k in ("kill", "restart") if k in row]
        at = row.get("at")
        if (len(kinds) != 1 or set(row) != {"at", kinds[0]}
                or not isinstance(at, (int, float)) or not last <= at <= 1.0):
            raise SpecError(f"a fault is {{at: a share of the window, kill or "
                            f"restart: [ids]}}, in order of time; not {row!r}")
        kind, names = kinds[0], row[kinds[0]]
        if (not isinstance(names, list) or not names
                or len(set(names)) != len(names)
                or any(n not in index for n in names)):
            raise SpecError(f"fault {row!r} names validators the roster lacks, "
                            f"none, or one twice")
        members = tuple(sorted(index[n] for n in names))
        if kind == "kill" and down & set(members):
            raise SpecError(f"fault {row!r} kills a validator that is down")
        if kind == "restart" and not set(members) <= down:
            raise SpecError(f"fault {row!r} restarts a validator that is up")
        down = down | set(members) if kind == "kill" else down - set(members)
        if len(down) > tolerated:
            raise SpecError(f"fault {row!r} leaves {len(down)} validators down "
                            f"where the roster tolerates {tolerated}")
        events.append(FaultEvent(float(at), kind, members))
        last = at
    return FaultSchedule(tuple(events), clients_return)


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
    """Run each program the cell can meet and warm-up's rounds do not,
    once, at the sizes the configuration's file lists (``warm_shapes``),
    through the program's own entry points.

    ``comb`` / ``dual_pow``: the exponentiation programs (the share and
    coin waves' sizes move with the BBA round count, each size bucket is
    a program, and the rare ones would otherwise be met first inside a
    window).  ``rs_mixed``: [matrices, shard length] of the RS decode
    column's three-step path, which a wave takes whose matrices were
    gathered from different senders (a validator that came level inside
    an epoch holds other ECHOes than its peers); the program does not
    bucket that path's batch axis, so every batch size is a set of
    programs (PERF.md section 7)."""
    if shapes.get("comb") or shapes.get("dual_pow"):
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
    for matrices, length in shapes.get("rs_mixed", ()):
        # the first k shards everywhere but in the last matrix: two
        # erasure patterns, so the fused program refuses the wave
        indices = np.tile(np.arange(crypto.k), (matrices, 1))
        indices[-1] += 1
        crypto.decode_recheck_batch(
            indices, np.zeros((matrices, crypto.k, length), dtype=np.uint8)
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
                held_at_settle: Sequence[Sequence[int]],
                served_at_settle: Sequence[Sequence[bool]] = (),
                adopted: Optional[Dict[str, List]] = None) -> Dict:
        """Plain data for the reference; taken before the cluster is
        stopped, since a graceful close flushes and a kill does not.
        ``held_at_settle[e]`` is ``held_bytes`` as it read when the
        harness stamped epoch e settled: what an acknowledged settle
        could count on, whatever reached the file afterwards.  Under a
        fault schedule, ``served_at_settle[e]`` says which validators
        were in service at that stamp (a log is held to the stamps of
        its validator's time in service), and ``adopted[nid]`` the
        [from, to) epochs a restarted validator may have taken over
        from its peers: from the frontier its log left it at, to the
        last epoch its peers had ordered or in flight (``pipeline_depth``
        from their ordered frontier on) when it came level."""
        logs = {}
        for i, (nid, held) in enumerate(zip(ids, self.held_bytes(ids))):
            logs[nid] = {
                "path": self._log(nid),
                "held_bytes": held,
                "held_at_settle": [row[i] for row in held_at_settle],
            }
            if adopted is not None:
                logs[nid]["served_at_settle"] = [
                    row[i] for row in served_at_settle
                ]
                logs[nid]["adopted"] = adopted.get(nid, [])
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
        try:
            self.faults = fault_schedule(cell.traffic, self.ids, self.cfg.f)
            if self.wal is None and self.faults is not None and any(
                ev.kind == "restart" for ev in self.faults.events
            ):
                raise SpecError("a restart comes back from the validator's "
                                "log: the configuration needs a wal_dir")
        except BaseException:
            self.close()
            raise
        # who is up (not killed, or restarted), who is in service (up and
        # level with the others), and whom the clients reach; without a
        # fault schedule all three are everybody, throughout
        everybody = range(len(self.ids))
        self._up = set(everybody)
        self._serving = set(everybody)
        self._reach = [True] * len(self.ids)
        self._answering = list(everybody)  # the indices ``_reach`` holds true
        self._up_nodes = self._serving_nodes = self._nodes
        self._next_fault = 0
        self._wave = 0  # delivery waves of the round that is running
        self._evicted_gone = 0  # by mempools that died with their process
        # what the fault schedule did, for the result and the reference
        self.fault_log: List[Dict] = []  # one row an event, as applied
        self.outages: List[Dict] = []  # one row a validator and kill
        self._outage: Dict[int, Dict] = {}  # of those down or catching up
        self.resubmitted = 0
        # of the window's submissions under a schedule: the arrival and
        # the validators (a bit each) whose OK it has
        self._arrivals: List[Arrival] = []
        self._acked_by: List[int] = []
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
        self.round_waves: List[int] = []  # delivery waves of every round
        # per settled epoch, what a crash would have left of each log
        # at the moment the epoch was stamped settled
        self.held_at_settle: List[List[int]] = []
        # ... and, under a fault schedule, who was in service then
        self.served_at_settle: List[tuple] = []
        self.clock = RoundClock(meter)

    # -- driving -------------------------------------------------------

    def _frontiers(self) -> tuple:
        serving = self._serving_nodes
        ordered = min(hb.merged_ordered_frontier for hb in serving)
        settled = min(hb.merged_settled_frontier for hb in serving)
        return ordered, settled

    def _stamp(self) -> None:
        """An epoch is ordered, or settled, once EVERY validator in
        service has crossed it."""
        ordered, settled = self._frontiers()
        now = time.perf_counter()
        while len(self.t_ordered) < ordered:
            self.t_ordered.append(now)
        if len(self.t_settled) < settled:
            held = [] if self.wal is None else self.wal.held_bytes(self.ids)
            while len(self.t_settled) < settled:
                self.t_settled.append(now)
                self.held_at_settle.append(held)
            if self.faults is not None:
                served = tuple(i in self._serving for i in range(len(self.ids)))
                while len(self.served_at_settle) < settled:
                    self.served_at_settle.append(served)
        if len(self._serving) < len(self._up):
            self._back_in_service(now)

    def _submit(self, a: Arrival, due: Optional[float]) -> None:
        node = a.nonce % len(self.ids)
        if not self._reach[node]:
            node = self._fail_over(a.nonce)
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
            if self.faults is not None:
                self._arrivals.append(a)
                self._acked_by.append(ok << node)

    def _round(self, between: Callable[[], None]) -> None:
        spans = self.spans
        net = self.cluster.net
        start = time.perf_counter()
        with self.clock.timed():
            with spans("start_epoch"):
                for hb in self._up_nodes:
                    hb.start_epoch()
            self._wave = 0
            while True:
                with spans("step"):
                    stepped = net.step()
                if not stepped:
                    # the manual-driving contract (ChannelNetwork.step):
                    # a drained queue needs the idle phase, and another
                    # pass if that produced traffic
                    with spans("idle_phase"):
                        net.idle_phase()
                self._wave += 1
                self._stamp()
                between()
                if not stepped and net.pending_count() == 0:
                    break
        self.rounds += 1
        self.round_waves.append(self._wave)
        self._wave = 0  # between rounds: before the next one's first wave
        self.round_log.append((
            start, time.perf_counter(),
            None if self.wal is None else self.wal.written_bytes(self.ids),
        ))

    def _quiet(self) -> bool:
        """Nothing waits to be proposed, every ordered epoch is settled,
        and no restarted validator is still catching up."""
        ordered, settled = self._frontiers()
        pending = sum(hb.pending_tx_count() for hb in self._up_nodes)
        return (pending == 0 and ordered == settled
                and len(self._serving) == len(self._up))

    def _drain(self) -> None:
        start = time.perf_counter()
        while not self._quiet() and (
            time.perf_counter() - start < drain_limit_s(self.clock.longest_s)
        ):
            self._round(lambda: None)

    # -- the fault schedule -----------------------------------------------

    def _membership_changed(self) -> None:
        self._up_nodes = [self._nodes[i] for i in sorted(self._up)]
        self._serving_nodes = [self._nodes[i] for i in sorted(self._serving)]
        back = (self._serving if self.faults.clients_return == "in_service"
                else self._up)
        self._reach = [self._clients_reach(i, back) for i in range(len(self.ids))]
        self._answering = [i for i, ok in enumerate(self._reach) if ok]

    def _clients_reach(self, i: int, back: set) -> bool:
        """Does a client's connection to validator ``i`` get through?"""
        return i in back

    def _fail_over(self, nonce: int) -> int:
        """Whom the client of a validator that refuses the connection
        tries instead, at once (no timeout is modelled): one of its own
        choosing, so that the refused transactions spread evenly over
        the validators that answer.  (The next address in id order would
        send the refused 5/16 of the cell's load to one validator: a hot
        spot of the client's making, PERF.md section 6.)"""
        answer = self._answering
        return answer[(nonce // len(self.ids)) % len(answer)]

    def _apply_faults(self, now: float, seconds: float) -> None:
        """Every event whose time has come, in order; ``_round`` calls
        this between delivery waves, so an event as a rule falls inside
        an epoch with frames in flight (an idle system takes it at
        once)."""
        events = self.faults.events
        while (self._next_fault < len(events)
               and events[self._next_fault].at * seconds <= now):
            ev = events[self._next_fault]
            self._next_fault += 1
            row = {
                "kind": ev.kind,
                "nodes": [self.ids[i] for i in ev.members],
                "due_s": ev.at * seconds,
                "t": time.perf_counter(),
                "round": self.rounds,
                "wave": self._wave,
                "epochs_ordered": len(self.t_ordered),
                "epochs_settled": len(self.t_settled),
                "submissions": len(self.submissions),
            }
            self.fault_log.append(row)
            if ev.kind == "kill":
                self._kill(ev.members)
                row["resubmitted"] = self._resubmit()
            else:
                self._restart(ev.members, row)
            row["took_s"] = time.perf_counter() - row["t"]

    def _kill(self, members: Sequence[int]) -> None:
        for i in members:
            nid = self.ids[i]
            self.cluster.crash(nid)  # its frames in flight die with it
            self._up.discard(i)
            self._serving.discard(i)
            self._outage[i] = {
                "node": nid,
                "t_kill": time.perf_counter(),
                "ordered_at_kill": self._nodes[i].merged_ordered_frontier,
                "settled_at_kill": self._nodes[i].merged_settled_frontier,
            }
            self.outages.append(self._outage[i])
        self._membership_changed()

    def _resubmit(self) -> int:
        """The clients of the validators that just went: a mempool is
        memory, so what only validators now out of reach have
        acknowledged, and no epoch stamped so far has settled, is the
        client's to send again (it saw its connection drop).  At once,
        round robin over the validators that answer.  It stays one
        attempt, timed from its first due time."""
        gone = sum(1 << i for i, ok in enumerate(self._reach) if not ok)
        waiting = {
            self.timed[k]: k
            for k, by in enumerate(self._acked_by) if by and not by & ~gone
        }
        ledger = self._serving_nodes[0].merged_batches
        for epoch in range(len(self.t_settled)):
            for txs in ledger[epoch].contributions.values():
                for tx in txs:
                    waiting.pop(tx, None)
        answer = self._answering
        for turn, k in enumerate(sorted(waiting.values())):
            a, node = self._arrivals[k], answer[turn % len(answer)]
            ack = self._ingress[node].submit(a.client, a.nonce, a.fee, a.tx)
            ok = int(ack.status) == self._ok
            self.submissions.append((a.tx, self.ids[node], ok))
            self._acked_by[k] |= ok << node
        self.resubmitted += len(waiting)
        return len(waiting)

    def _restart(self, members: Sequence[int], event: Dict) -> None:
        """Each validator's process comes back from its log, in id
        order, and asks its peers for what it missed, as
        ``ValidatorHost.listen`` does for a node whose log left it past
        epoch 0 (transport/host.py).  The harness takes the new
        ``HoneyBadger`` and the new ingress plane in place of the old."""
        for i in members:
            nid = self.ids[i]
            self._evicted_gone += self._nodes[i].mempool.evicted
            t1 = time.perf_counter()
            hb = self.cluster.restart_node(nid)
            t2 = time.perf_counter()
            self._nodes[i] = hb
            self._ingress[i] = self.cluster.ingress(nid)
            hb.request_catchup()
            self._up.add(i)
            self._outage[i].update(
                t_restart_event=event["t"],
                round_restart=event["round"],
                t_restart=t1,
                replay_s=t2 - t1,
                settled_at_restart=hb.merged_settled_frontier,
                ordered_epochs_at_restart=len(self.t_ordered),
            )
        self._membership_changed()

    def _back_in_service(self, now: float) -> None:
        """A restarted validator is in service again from the moment its
        settled frontier equals the highest of those in service."""
        top = max(hb.merged_settled_frontier for hb in self._serving_nodes)
        back = [
            i for i in sorted(self._up - self._serving)
            if self._nodes[i].merged_settled_frontier >= top
        ]
        # the epochs its peers have ordered, or have in flight, at this
        # moment started without it: it may yet adopt those
        adopted_to = self.cfg.pipeline_depth + max(
            hb.merged_ordered_frontier for hb in self._serving_nodes
        )
        for i in back:
            self._serving.add(i)
            self._outage.pop(i).update(
                t_in_service=now,
                settled_in_service=self._nodes[i].merged_settled_frontier,
                adopted_to=adopted_to,
                round_in_service=self.rounds,
                submissions_in_service=len(self.submissions),
            )
        if back:
            self._membership_changed()

    def fault_report(self) -> Optional[Dict]:
        """What the schedule did, as plain data for the result line's
        metrics: the events as applied, each outage, the waves of every
        round; None without a schedule."""
        if self.faults is None:
            return None
        return {
            "events": self.fault_log,
            "outages": self.outages,
            "resubmitted": self.resubmitted,
            "round_waves": self.round_waves,
            "never_back": sorted(
                self.ids[i] for i in self._up - self._serving
            ),
        }

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
            if self.faults is not None:
                self._apply_faults(now, seconds)

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
        # the fallback: where one round ran from before the stretch the
        # trace starts in to the window's end, no boundary fell inside
        # it, and the trace starts here, over the drain's rounds
        tick(t_end - t0)
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
        obs = {
            "node_ids": list(self.ids),
            "submissions": self.submissions,
            "ledgers": {
                nid: [b.contributions for b in hb.merged_batches]
                for nid, hb in zip(self.ids, self._nodes)
            },
            "evicted": self._evicted_gone + sum(
                hb.mempool.evicted for hb in self._nodes
            ),
            "ordered": {
                nid: hb.merged_ordered_frontier
                for nid, hb in zip(self.ids, self._nodes)
            },
            "settled": {
                nid: hb.merged_settled_frontier
                for nid, hb in zip(self.ids, self._nodes)
            },
            "batch_size": max(self.cfg.batch_size, self.cfg.n),
            "wal": None,
        }
        adopted = None
        if self.faults is not None:
            obs["faults"] = {
                "pipeline_depth": self.cfg.pipeline_depth,
                "outages": self.outages,
                "down_at_rest": [
                    nid for i, nid in enumerate(self.ids) if i not in self._up
                ],
            }
            # one that is never back in service is still adopting
            adopted = {
                nid: [[o["settled_at_restart"],
                       o.get("adopted_to", len(self.t_settled))]
                      for o in self.outages
                      if o["node"] == nid and "settled_at_restart" in o]
                for nid in self.ids
            }
        if self.wal is not None:
            obs["wal"] = self.wal.observe(
                self.ids, self.held_at_settle, self.served_at_settle, adopted
            )
        return obs

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
           "drain_limit_s", "fresh_log_dir", "fault_schedule", "FaultSchedule",
           "FaultEvent", "CLIENTS_RETURN"]
