"""CATCHUP sends a requester each batch body once, from one payload
built once.

What a responder does with a ``CatchupReq`` is read from the request
itself against the two numbers it keeps a requester
(``_catchup_floor``: how far it has served; ``_catchup_last_req``: the
last ``from_epoch`` asked):

- at or past the floor: the next window, unconditionally;
- advanced since the last request but inside the floor: a requester
  adopting what is in flight to it; it buys what is past the floor and
  draws no budget;
- not advanced: a retry, a replay or a loop; the whole window again,
  ``CATCHUP_REPEAT_BUDGET`` times, then refusal (re-armed by a local
  epoch advance and by ``peer_reconnected``).

An epoch's ``CatchupRespPayload`` is built at its first serve and
handed to every later requester as the same object.
"""

import collections
import hashlib

import pytest

from cleisthenes_tpu.config import Config
from cleisthenes_tpu.core.batch import Batch
from cleisthenes_tpu.core.ledger import encode_batch_body
from cleisthenes_tpu.protocol.cluster import SimulatedCluster
from cleisthenes_tpu.protocol.honeybadger import (
    CATCHUP_BODY_MEMO_EPOCHS,
    CATCHUP_MAX_EPOCHS,
    CATCHUP_REPEAT_BUDGET,
    HoneyBadger,
    setup_keys,
)
from cleisthenes_tpu.transport.broadcast import ChannelBroadcaster
from cleisthenes_tpu.transport.channel import ChannelNetwork
from cleisthenes_tpu.transport.message import (
    CatchupReqPayload,
    CatchupRespPayload,
)

IDS = [f"node{i}" for i in range(4)]
ME, ASKER, OTHER = IDS[3], IDS[0], IDS[1]


def _batch(epoch):
    return Batch(contributions={ASKER: [b"e%d" % epoch]})


class _Responder:
    """One validator holding ``depth`` committed batches, every
    ``CatchupRespPayload`` it sends kept as (receiver, payload)."""

    def __init__(self, depth):
        cfg = Config(n=4, batch_size=8)
        keys = setup_keys(cfg, IDS, seed=93)
        net = ChannelNetwork()
        self.hb = hb = HoneyBadger(
            config=cfg,
            node_id=ME,
            member_ids=IDS,
            keys=keys[ME],
            out=ChannelBroadcaster(net, ME, IDS),
            auto_propose=False,
        )
        net.join(ME, hb, None)
        self.sent = []
        real_send = hb.out.send_to

        def send_to(member, payload):
            if isinstance(payload, CatchupRespPayload):
                self.sent.append((member, payload))
            real_send(member, payload)

        hb.out.send_to = send_to
        self.settle(depth)

    def settle(self, upto):
        """Adopt epochs up to ``upto`` on f+1 = 2 identical bodies, as
        a validator that is itself catching up does."""
        hb = self.hb
        for epoch in range(len(hb.committed_batches), upto):
            body = encode_batch_body(epoch, _batch(epoch))
            for peer in IDS[1:3]:
                hb._handle_catchup_resp(
                    peer, CatchupRespPayload(epoch, body)
                )
        assert len(hb.committed_batches) == upto

    def ask(self, sender, from_epoch):
        """The epochs of the bodies this request bought."""
        before = len(self.sent)
        self.hb._handle_catchup_req(sender, CatchupReqPayload(from_epoch))
        bought = self.sent[before:]
        assert all(member == sender for member, _p in bought)
        return [p.epoch for _m, p in bought]

    def counters(self):
        return self.hb.metrics.snapshot()["catchup"]


# ---------------------------------------------------------------------------
# the serving rule
# ---------------------------------------------------------------------------


def test_advancing_request_buys_only_new_epochs_and_no_budget():
    r = _Responder(4)
    assert r.ask(ASKER, 0) == [0, 1, 2, 3]
    # the requester adopts what it was sent and asks at its new
    # frontier: nothing of the window goes out again
    assert r.ask(ASKER, 1) == []
    assert r.counters()["bodies_in_flight_skipped"] == 3
    assert ASKER not in r.hb._catchup_repeats
    # two epochs settle here meanwhile: the next advancing request
    # buys them, and them alone
    r.settle(6)
    assert r.ask(ASKER, 3) == [4, 5]
    assert r.ask(ASKER, 5) == []
    assert r.counters()["bodies_in_flight_skipped"] == 3 + 1 + 1
    assert r.counters()["bodies_served"] == 6
    # none of it drew from the repeat budget
    assert ASKER not in r.hb._catchup_repeats
    # a requester level with the floor gets the next window as before
    r.settle(7)
    assert r.ask(ASKER, 6) == [6]


def test_advancing_request_reaches_past_the_serving_cap():
    """A requester more than one window behind: its advancing requests
    slide the window on, each epoch sent once."""
    r = _Responder(CATCHUP_MAX_EPOCHS + 8)
    assert r.ask(ASKER, 0) == list(range(CATCHUP_MAX_EPOCHS))
    assert r.ask(ASKER, 5) == list(
        range(CATCHUP_MAX_EPOCHS, CATCHUP_MAX_EPOCHS + 5)
    )
    assert r.ask(ASKER, 20) == list(
        range(CATCHUP_MAX_EPOCHS + 5, CATCHUP_MAX_EPOCHS + 8)
    )
    epochs = [p.epoch for _m, p in r.sent]
    assert sorted(epochs) == list(range(CATCHUP_MAX_EPOCHS + 8))


@pytest.mark.parametrize("again", ["same", "behind"])
def test_repeat_draws_the_budget_and_is_then_refused(again):
    r = _Responder(5)
    assert r.ask(ASKER, 2) == [2, 3, 4]
    start = 2 if again == "same" else 1
    window = list(range(start, 5))
    for left in reversed(range(CATCHUP_REPEAT_BUDGET)):
        assert r.ask(ASKER, start) == window
        assert r.hb._catchup_repeats[ASKER] == left
    assert r.ask(ASKER, start) == []
    assert r.ask(ASKER, start) == []
    assert r.counters()["bodies_in_flight_skipped"] == 0
    # another requester has a budget of its own
    assert r.ask(OTHER, start) == window


def test_a_requester_stuck_inside_the_window_gets_it_on_its_retry():
    """It advanced (so its request bought nothing), the rest of the
    window never reached it, and it asks again at the same frontier:
    that is the budgeted case, and buys the window from there."""
    r = _Responder(6)
    assert r.ask(ASKER, 0) == [0, 1, 2, 3, 4, 5]
    assert r.ask(ASKER, 2) == []
    assert r.ask(ASKER, 2) == [2, 3, 4, 5]
    assert r.hb._catchup_repeats[ASKER] == CATCHUP_REPEAT_BUDGET - 1
    # a loop that alternates two frontiers buys no more than one that
    # repeats one: every second request is a repeat and draws
    bought = 0
    for _ in range(8):
        bought += len(r.ask(ASKER, 1)) + len(r.ask(ASKER, 2))
    assert bought == (CATCHUP_REPEAT_BUDGET - 1) * 5


def test_link_heal_rearms_and_reserves_the_last_window():
    r = _Responder(4)
    assert r.ask(ASKER, 0) == [0, 1, 2, 3]
    assert r.ask(ASKER, 2) == []  # adopting; the rest was lost
    before = len(r.sent)
    r.hb.peer_reconnected(ASKER)
    assert [p.epoch for _m, p in r.sent[before:]] == [2, 3]
    assert ASKER not in r.hb._catchup_repeats


def test_own_request_looped_back_is_not_served():
    r = _Responder(3)
    assert r.ask(ME, 0) == []
    assert r.counters()["responses_served"] == 0
    assert ME not in r.hb._catchup_last_req


def test_parked_reserve_sends_each_body_once():
    """A request at our own frontier is parked and answered when we
    settle past it; the requester's next requests are advancing ones.
    Over the whole exchange every body goes to it once."""
    r = _Responder(2)
    assert r.ask(ASKER, 2) == []
    assert r.hb._catchup_parked == {ASKER: 2}
    r.settle(5)  # the park is answered at the first settle past it
    assert [p.epoch for _m, p in r.sent] == [2]
    assert r.hb._catchup_parked == {}
    assert r.ask(ASKER, 3) == [3, 4]
    assert r.ask(ASKER, 4) == []
    assert r.ask(ASKER, 5) == []  # parked again, at the new frontier
    r.settle(6)
    counted = collections.Counter(p.epoch for _m, p in r.sent)
    assert counted == {2: 1, 3: 1, 4: 1, 5: 1}
    assert ASKER not in r.hb._catchup_repeats


# ---------------------------------------------------------------------------
# one payload an epoch
# ---------------------------------------------------------------------------


def test_two_requesters_are_handed_the_same_payload_object():
    r = _Responder(3)
    r.ask(ASKER, 0)
    r.ask(OTHER, 0)
    first = [p for m, p in r.sent if m == ASKER]
    second = [p for m, p in r.sent if m == OTHER]
    assert len(first) == len(second) == 3
    assert all(a is b for a, b in zip(first, second))
    for payload in first:
        assert payload.body == encode_batch_body(
            payload.epoch, r.hb.committed_batches[payload.epoch]
        )
    c = r.counters()
    assert (c["body_memo_misses"], c["body_memo_hits"]) == (3, 3)
    assert c["bodies_served"] == 6


def test_body_memo_is_bounded_and_refills():
    """A requester that asks for old epochs cannot grow it: oldest
    insertion out first, and an evicted epoch is built again, to the
    same bytes."""
    depth = CATCHUP_BODY_MEMO_EPOCHS + CATCHUP_MAX_EPOCHS
    r = _Responder(depth)
    for start in range(0, depth, CATCHUP_MAX_EPOCHS):
        assert len(r.ask(ASKER, start)) == CATCHUP_MAX_EPOCHS
    memo = r.hb._catchup_body_memo.map
    assert len(memo) == CATCHUP_BODY_MEMO_EPOCHS
    assert sorted(memo) == list(range(CATCHUP_MAX_EPOCHS, depth))
    assert r.counters()["body_memo_misses"] == depth
    first = r.sent[0][1]
    assert r.ask(OTHER, 0)[0] == 0
    again = r.sent[depth][1]
    assert again is not first and again == first
    assert len(memo) == CATCHUP_BODY_MEMO_EPOCHS


# ---------------------------------------------------------------------------
# whole clusters
# ---------------------------------------------------------------------------


def _digests(cluster):
    out = set()
    for nid in cluster.ids:
        h = hashlib.sha256()
        for epoch, batch in enumerate(cluster.nodes[nid].committed_batches):
            h.update(encode_batch_body(epoch, batch))
        out.add((len(cluster.nodes[nid].committed_batches), h.hexdigest()))
    return out


class _Fold:
    """``tests/test_catchup_requeue.py``'s cluster with f validators
    killed at once, driven as the benchmark's fold drives it."""

    def __init__(self, tmp_path, n, trace=False):
        self.cluster = SimulatedCluster(
            config=Config(
                n=n, batch_size=4 * n, seed=11, mempool_capacity=4096,
                trace=trace,
            ),
            seed=11,
            key_seed=3,
            wal_dir=str(tmp_path),
            auto_propose=False,
        )
        self.ids = self.cluster.ids
        f = self.cluster.config.f
        self.victims = self.ids[-f:]
        self.survivors = self.ids[:-f]
        self._nonce = 0

    def submit(self, nid, count):
        ingress = self.cluster.ingress(nid)
        for _ in range(count):
            k = self._nonce
            self._nonce += 1
            tx = b"sv-%06d-" % k + b"x" * 20
            ingress.submit(f"client{k % 50}", k, 1 + k % 7, tx)

    def outage(self, rounds):
        c = self.cluster
        for nid in self.ids:
            self.submit(nid, 6)
        c.run_until_drained()
        for nid in self.victims:
            c.crash(nid)
        for _ in range(rounds):
            for nid in self.survivors:
                self.submit(nid, 5)
            c.run_until_drained(skip=tuple(self.victims))

    def catchup(self):
        return {
            nid: self.cluster.nodes[nid].metrics.snapshot()["catchup"]
            for nid in self.ids
        }


@pytest.mark.parametrize("clients_return", ["in_service", "at_restart"])
def test_kill_and_restart_of_f_serves_each_body_once(
    tmp_path, clients_return
):
    """Sixteen validators, five killed for twenty epochs and
    restarted from their logs: what the fifteen peers of a requester
    serve it, over what it adopts, is at most one body from each and
    the epoch that parked requests are answered with at the end."""
    fold = _Fold(tmp_path, 16)
    c = fold.cluster
    try:
        fold.outage(rounds=20)
        for nid in fold.victims:
            hb = c.restart_node(nid)
            if clients_return == "at_restart":
                fold.submit(nid, 10)
            hb.request_catchup()  # as ValidatorHost.listen does
        for nid in fold.ids:
            c.nodes[nid].start_epoch()
        c.net.run()
        for nid in fold.survivors:
            fold.submit(nid, 3)
        c.run_until_drained(max_rounds=80)
        assert len(_digests(c)) == 1  # sixteen ledgers level
        assert all(hb.pending_tx_count() == 0 for hb in c.nodes.values())
        snaps = fold.catchup()
        served = sum(s["bodies_served"] for s in snaps.values())
        adopted = sum(s["bodies_adopted"] for s in snaps.values())
        assert adopted >= 20 * len(fold.victims)
        responders = len(fold.ids) - 1
        assert served / adopted <= responders + 1
        # every body a validator serves is encoded once
        for nid, s in snaps.items():
            assert s["body_memo_misses"] <= len(
                c.nodes[nid].committed_batches
            )
            assert (
                s["body_memo_hits"] + s["body_memo_misses"]
                == s["bodies_served"]
            )
        assert sum(s["bodies_in_flight_skipped"] for s in snaps.values()) > 0
        assert sum(s["body_memo_hits"] for s in snaps.values()) > 0
    finally:
        c.stop()


@pytest.mark.parametrize("repair", ["retry", "heal"])
def test_requester_comes_level_after_lost_responses(tmp_path, repair):
    """N=4, f=1: the restarted validator needs two identical bodies an
    epoch, and everything two of its three peers send it is dropped.
    It adopts nothing; once the links carry again, its retry at the
    same frontier (the budgeted case) or the peers' link-heal event
    brings the windows again and it comes level."""
    fold = _Fold(tmp_path, 4, trace=True)
    c = fold.cluster
    try:
        fold.outage(rounds=5)
        victim = fold.victims[0]
        deaf_to = set(fold.survivors[1:])
        c.fault_filter = lambda sender, receiver, wire: (
            None if receiver == victim and sender in deaf_to else wire
        )
        hb = c.restart_node(victim)
        ahead = len(c.nodes[fold.survivors[0]].committed_batches)
        behind = len(hb.committed_batches)
        assert ahead - behind >= 5
        hb.request_catchup()
        c.net.run()
        assert len(hb.committed_batches) == behind  # one vote an epoch
        c.fault_filter = None
        if repair == "retry":
            hb.request_catchup()
        else:
            for nid in sorted(deaf_to):
                c.nodes[nid].peer_reconnected(victim)
        c.net.run()
        assert len(hb.committed_batches) == ahead
        assert len(_digests(c)) == 1
        snaps = fold.catchup()
        assert snaps[victim]["bodies_adopted"] == ahead - behind
        # the span says what each answer held and what it left out
        serves = [
            args
            for nid in fold.survivors
            for _seq, _ts, dur, cat, name, args
            in c.nodes[nid].trace.events()
            if (cat, name) == ("catchup", "serve") and dur is not None
        ]
        assert sum(a["bodies"] for a in serves) == sum(
            snaps[nid]["bodies_served"] for nid in fold.survivors
        )
        assert sum(a["skipped"] for a in serves) == sum(
            snaps[nid]["bodies_in_flight_skipped"] for nid in fold.survivors
        )
    finally:
        c.stop()
