"""A restarted validator keeps what it acknowledged.

A validator that comes back from its log is behind; until CATCHUP has
brought it level, whatever it proposes goes into an epoch its peers
closed long ago, and that epoch's state is dropped when the batch is
adopted.  The transactions it had taken out of its queue for that
proposal were acknowledged OK at ingress, so they go back on the queue
(``HoneyBadger._requeue_own``, shared with ``_commit_batch``).

- a restarted validator with clients on it, as a deployment drives it
  (``auto_propose=True``: it proposes after every adopted epoch) and as
  the benchmark's fold does (``auto_propose=False``: one
  ``start_epoch()`` a round), N=4 f=1 and N=7 f=2, both commit arms;
- one validator alone, fed the CATCHUP bodies by hand: the coupled
  arm, the two-frontier arm (ordering adopted first, plaintext later),
  a proposal the adopted batch holds whole;
- the ``catchup`` counters and spans, and ``ledger/replay``.
"""

import collections
import hashlib

import pytest

from cleisthenes_tpu.config import Config
from cleisthenes_tpu.core.batch import Batch
from cleisthenes_tpu.core.ledger import (
    BatchLog,
    encode_batch_body,
    encode_ordered_body,
)
from cleisthenes_tpu.protocol.cluster import HOLDS, SimulatedCluster
from cleisthenes_tpu.protocol.honeybadger import HoneyBadger, setup_keys
from cleisthenes_tpu.transport.broadcast import ChannelBroadcaster
from cleisthenes_tpu.transport.channel import ChannelNetwork
from cleisthenes_tpu.transport.message import (
    CatchupOrdPayload,
    CatchupRespPayload,
    IngressStatus,
)

MISSED_ROUNDS = 5  # rounds the survivors run without the victim
BEHIND_TXS = 30  # submitted to the victim between restart and level


class _Outage:
    """One validator crashed for several epochs, restarted from its
    log, and given transactions before it is level."""

    def __init__(self, tmp_path, n, order_then_settle, auto_propose,
                 trace=False):
        self.cluster = SimulatedCluster(
            config=Config(
                n=n,
                batch_size=4 * n,
                seed=11,
                mempool_capacity=4096,
                order_then_settle=order_then_settle,
                trace=trace,
            ),
            seed=11,
            key_seed=3,
            wal_dir=str(tmp_path),
            auto_propose=auto_propose,
        )
        self.ids = self.cluster.ids
        self.victim = self.ids[-1]
        self.survivors = self.ids[:-1]
        self.acked = {}  # tx -> the validators that answered OK
        self._nonce = 0

    def submit(self, nid, count):
        ingress = self.cluster.ingress(nid)
        for _ in range(count):
            k = self._nonce
            self._nonce += 1
            tx = b"rq-%06d-" % k + b"x" * 20
            ack = ingress.submit(f"client{k % 50}", k, 1 + k % 7, tx)
            if int(ack.status) == int(IngressStatus.OK):
                self.acked.setdefault(tx, set()).add(nid)

    def run(self):
        """Returns (epochs missed, the restarted validator)."""
        c = self.cluster
        for nid in self.ids:
            self.submit(nid, 6)
        c.run_until_drained()
        c.crash(self.victim)
        for _ in range(MISSED_ROUNDS):
            for nid in self.survivors:
                self.submit(nid, 5)
            c.run_until_drained(skip=(self.victim,))
        ahead = len(c.nodes[self.survivors[0]].committed_batches)
        hb = c.restart_node(self.victim)
        self.from_log = len(hb.committed_batches)
        missed = ahead - self.from_log
        assert missed >= MISSED_ROUNDS
        self.submit(self.victim, BEHIND_TXS)
        assert len(self.acked) == self._nonce  # nothing was refused
        hb.request_catchup()  # as ValidatorHost.listen does
        if c.nodes[self.survivors[0]].auto_propose:
            hb.start_epoch()  # a deployment: the ingress kick
        else:
            for nid in self.ids:  # the fold: one kick a round, everybody
                c.nodes[nid].start_epoch()
        c.net.run()
        for nid in self.survivors:
            self.submit(nid, 3)
        c.run_until_drained(max_rounds=80)
        return missed, hb

    def audit(self):
        """Every OK-acked transaction is in exactly one settled batch
        on every validator, in the contribution of a validator that
        admitted it; all ledgers byte-identical."""
        c = self.cluster
        digests = set()
        for nid in self.ids:
            h = hashlib.sha256()
            for epoch, batch in enumerate(c.nodes[nid].committed_batches):
                h.update(encode_batch_body(epoch, batch))
            digests.add(h.hexdigest())
        assert len(digests) == 1
        settled = collections.Counter()
        homes = {}
        for batch in c.nodes[self.ids[0]].committed_batches:
            for proposer, txs in batch.contributions.items():
                for tx in txs:
                    settled[tx] += 1
                    homes[tx] = proposer
        lost = [tx for tx in self.acked if settled[tx] == 0]
        twice = [tx for tx in self.acked if settled[tx] > 1]
        misplaced = [
            tx for tx, by in self.acked.items()
            if settled[tx] == 1 and homes[tx] not in by
        ]
        assert (len(lost), len(twice), len(misplaced)) == (0, 0, 0)
        assert all(
            hb.pending_tx_count() == 0 for hb in c.nodes.values()
        )


@pytest.mark.parametrize("order_then_settle", [True, False])
@pytest.mark.parametrize("n", [4, 7])
def test_deployment_restart_keeps_acked_transactions(
    tmp_path, n, order_then_settle
):
    """(a) ``auto_propose=True``: the restarted validator proposes
    into every stale epoch it passes, and every one of those proposals
    is dropped by the next adoption (ISSUE 36's 72-of-72 case)."""
    outage = _Outage(tmp_path, n, order_then_settle, auto_propose=True)
    try:
        missed, hb = outage.run()
        outage.audit()
        catchup = hb.metrics.snapshot()["catchup"]
        assert catchup["bodies_adopted"] == missed
        # b/n = 4 transactions a proposal, one dropped an adopted epoch
        assert catchup["requeued_tx"] == 4 * missed
    finally:
        outage.cluster.stop()


@pytest.mark.parametrize("order_then_settle", [True, False])
@pytest.mark.parametrize("n", [4, 7])
def test_fold_restart_keeps_acked_transactions(
    tmp_path, n, order_then_settle
):
    """(b) driven as benchmarks/executors.py drives it: nobody
    proposes unasked, and the round's ``start_epoch()`` falls between
    the restart and the level."""
    outage = _Outage(tmp_path, n, order_then_settle, auto_propose=False)
    try:
        _missed, hb = outage.run()
        outage.audit()
        assert hb.metrics.snapshot()["catchup"]["requeued_tx"] == 4
    finally:
        outage.cluster.stop()


# ---------------------------------------------------------------------------
# one validator, fed by hand
# ---------------------------------------------------------------------------

IDS = [f"node{i}" for i in range(4)]


def _lone_validator(tmp_path, order_then_settle):
    """A validator that has proposed its whole queue into epoch 0."""
    cfg = Config(
        n=4, batch_size=16, seed=21, order_then_settle=order_then_settle
    )
    keys = setup_keys(cfg, IDS, seed=44)
    net = ChannelNetwork()
    hb = HoneyBadger(
        config=cfg,
        node_id=IDS[0],
        member_ids=IDS,
        keys=keys[IDS[0]],
        out=ChannelBroadcaster(net, IDS[0], IDS),
        auto_propose=False,
        batch_log=BatchLog(str(tmp_path / "lone.log")),
    )
    net.join(IDS[0], hb, None)
    for i in range(4):
        hb.add_transaction(b"own-%d" % i)
    hb.start_epoch()
    mine = list(hb._epochs[0].my_txs)
    assert sorted(mine) == [b"own-%d" % i for i in range(4)]
    assert hb.pending_tx_count() == 0
    return hb, mine


def _feed_batch(hb, epoch, batch):
    """f+1 = 2 byte-identical CLOG bodies: the adoption rule."""
    body = encode_batch_body(epoch, batch)
    for sender in IDS[1:3]:
        hb._handle_catchup_resp(
            sender, CatchupRespPayload(epoch=epoch, body=body)
        )


def _queued(hb):
    out = []
    while len(hb.que):
        out.append(hb.que.poll())
    return out


@pytest.mark.parametrize("order_then_settle", [True, False])
def test_adoption_requeues_what_the_batch_left_out(
    tmp_path, order_then_settle
):
    """(d) both commit arms: the batch the peers settled holds one of
    our four transactions (under our name) and one under another
    validator's; the other two go back on the queue in proposal
    order."""
    hb, mine = _lone_validator(tmp_path, order_then_settle)
    batch = Batch({IDS[0]: [mine[1]], IDS[1]: [b"theirs", mine[3]]})
    _feed_batch(hb, 0, batch)
    assert hb.settled_epoch == 1 and 0 not in hb._epochs
    assert _queued(hb) == [mine[0], mine[2]]
    assert hb.metrics.snapshot()["catchup"]["requeued_tx"] == 2
    hb.batch_log.close()


def test_ordering_adopted_first_then_plaintext(tmp_path):
    """(d) the two-frontier branch: the epoch's ORDERING is adopted
    first (``_adopt_ordered`` keeps the state we proposed into, and
    re-queues nothing yet), its plaintext arrives later as a CLOG body
    for an epoch below the ordered frontier."""
    hb, mine = _lone_validator(tmp_path, True)
    body = encode_ordered_body(0, {IDS[1]: b"ct-1", IDS[2]: b"ct-2"})
    for sender in IDS[1:3]:
        hb._handle_catchup_ord(
            sender, CatchupOrdPayload(epoch=0, body=body)
        )
    assert hb.epoch == 1 and hb.settled_epoch == 0
    assert hb._epochs[0].my_txs == mine and hb.pending_tx_count() == 0
    _feed_batch(hb, 0, Batch({IDS[1]: [b"theirs"], IDS[2]: [mine[0]]}))
    assert hb.settled_epoch == 1
    assert _queued(hb) == mine[1:]
    assert hb.metrics.snapshot()["catchup"]["requeued_tx"] == 3
    hb.batch_log.close()


@pytest.mark.parametrize("order_then_settle", [True, False])
def test_proposal_the_batch_holds_whole_requeues_nothing(
    tmp_path, order_then_settle
):
    """(c) nothing goes back, and a later duplicate submission of a
    settled transaction is filtered at poll time: nothing settles
    twice."""
    hb, mine = _lone_validator(tmp_path, order_then_settle)
    _feed_batch(hb, 0, Batch({IDS[0]: mine[:2], IDS[3]: mine[2:]}))
    assert hb.pending_tx_count() == 0
    assert hb.metrics.snapshot()["catchup"]["requeued_tx"] == 0
    hb.add_transaction(mine[0])
    hb.add_transaction(b"fresh")
    hb.start_epoch()
    assert hb._epochs[1].my_txs == [b"fresh"]
    hb.batch_log.close()


def test_a_state_we_never_proposed_into_requeues_nothing(tmp_path):
    """A validator that is merely behind (peer traffic opened the
    epoch's state, it proposed nothing) adopts as before."""
    hb, _mine = _lone_validator(tmp_path, False)
    _feed_batch(hb, 0, Batch({IDS[1]: [b"a"]}))
    hb._epoch_state(1)  # opened by traffic, no proposal of ours
    _queued(hb)
    _feed_batch(hb, 1, Batch({IDS[1]: [b"b"]}))
    assert hb.settled_epoch == 2 and hb.pending_tx_count() == 0
    hb.batch_log.close()


# ---------------------------------------------------------------------------
# counters and spans
# ---------------------------------------------------------------------------


def _log_records(path):
    with open(path, "rb") as fh:
        data = fh.read()
    records = list(BatchLog._scan(data))
    return len(records), records[-1][0]


def test_catchup_counters_and_spans(tmp_path):
    """(e) ``bodies_adopted`` is the epochs missed, on the counter and
    as ``catchup/adopt`` spans; ``ledger/replay`` reads what the log
    held; the survivors count what they served."""
    outage = _Outage(tmp_path, 4, True, auto_propose=True, trace=True)
    try:
        c = outage.cluster
        held = {}
        real_restart = c.restart_node

        def restart(nid):
            held[nid] = _log_records(str(tmp_path / f"{nid}.log"))
            return real_restart(nid)

        c.restart_node = restart
        missed, hb = outage.run()
        outage.audit()
        snap = hb.metrics.snapshot()["catchup"]
        assert snap["bodies_adopted"] == missed
        assert snap["requests_sent"] >= 1
        # what its log gave back: every batch it had settled
        assert snap["replayed_records"] == outage.from_log > 0
        events = hb.trace.events()
        spans = collections.defaultdict(list)
        for _seq, _ts, dur, cat, name, args in events:
            if cat in ("catchup", "ledger") and dur is not None:
                spans[f"{cat}/{name}"].append(args)
        adopts = spans["catchup/adopt"]
        assert len(adopts) == missed
        assert sum(a["requeued"] for a in adopts) == snap["requeued_tx"] > 0
        assert len(spans["catchup/request"]) == snap["requests_sent"]
        replays = spans["ledger/replay"]
        assert replays  # one a read of the log after construction
        records, length = held[outage.victim]
        assert all(
            (a["records"], a["bytes"]) == (records, length) for a in replays
        )
        served = [
            c.nodes[nid].metrics.snapshot()["catchup"]
            for nid in outage.survivors
        ]
        assert all(s["bodies_served"] >= missed for s in served)
        assert all(
            s["responses_served"] >= 1 and s["bodies_adopted"] == 0
            for s in served
        )
        serve_spans = [
            args
            for nid in outage.survivors
            for _seq, _ts, dur, cat, name, args
            in c.nodes[nid].trace.events()
            if (cat, name) == ("catchup", "serve") and dur is not None
        ]
        assert sum(a["bodies"] for a in serve_spans) == sum(
            s["bodies_served"] for s in served
        )
    finally:
        outage.cluster.stop()


def test_catchup_block_is_zeroed_on_a_node_that_never_restarted():
    from cleisthenes_tpu.utils.metrics import Metrics

    assert Metrics().snapshot()["catchup"] == {
        "requests_sent": 0,
        "responses_served": 0,
        "bodies_served": 0,
        "bodies_in_flight_skipped": 0,
        "body_memo_hits": 0,
        "body_memo_misses": 0,
        "bodies_adopted": 0,
        "requeued_tx": 0,
        "replayed_records": 0,
    }


def test_a_deployment_may_name_what_it_relies_on_across_a_restart():
    """``SimulatedCluster(requires=...)``: names this program holds
    build the cluster it would have built; one it does not hold is
    refused before the keys are dealt."""
    assert "requeue_at_adoption" in HOLDS
    c = SimulatedCluster(n=4, requires=["requeue_at_adoption"])
    try:
        assert len(c.nodes) == 4
    finally:
        c.stop()
    with pytest.raises(ValueError, match="serve_range_once"):
        SimulatedCluster(
            n=4, requires=["requeue_at_adoption", "serve_range_once"]
        )
