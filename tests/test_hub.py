"""CryptoHub: cross-instance batching of the live protocol hot path.

VERDICT.md round-1 item 3: the live path must use the batched kernels.
These tests prove (a) batched verification agrees with single-shot
verification, (b) a full epoch's crypto goes through the hub in FEW
batched dispatches instead of per-message singletons, and (c) invalid
work is rejected identically through the batched path.
"""

import numpy as np
import pytest

from cleisthenes_tpu.config import Config
from cleisthenes_tpu.ops import tpke
from cleisthenes_tpu.ops.backend import BatchCrypto
from cleisthenes_tpu.ops.coin import CommonCoin
from cleisthenes_tpu.protocol.hub import CryptoHub, HubWave, _Memo


class TestVerifyShareGroups:
    """The multi-group dual-pow fold (one dispatch for TPKE + coins)."""

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_groups_agree_with_single_calls(self, backend):
        pub_a, shares_a = tpke.deal(4, 2, seed=21)
        pub_b, shares_b = tpke.deal(7, 3, seed=22)
        svc_a = tpke.Tpke(pub_a)
        ct = svc_a.encrypt(b"group-a")
        dss = [svc_a.dec_share(s, ct) for s in shares_a]
        coin = CommonCoin(pub_b)
        cid = b"epoch|0"
        css = [coin.share(s, cid) for s in shares_b]
        # corrupt one share in each group
        dss[1] = tpke.DhShare(dss[1].index, dss[1].d, dss[1].e, dss[1].z + 1)
        css[4] = tpke.DhShare(css[4].index, css[4].d + 1, css[4].e, css[4].z)

        ga = (pub_a, ct.c1, dss, svc_a.context(ct))
        pub_c, base_c, ctx_c = coin.group_params(cid)
        gb = (pub_c, base_c, css, ctx_c)
        combined = tpke.verify_share_groups(
            [(ga[0], ga[1], ga[2], ga[3]), (gb[0], gb[1], gb[2], gb[3])],
            backend=backend,
        )
        singles = [
            tpke.verify_shares(ga[0], ga[1], ga[2], ga[3], backend="cpu"),
            tpke.verify_shares(gb[0], gb[1], gb[2], gb[3], backend="cpu"),
        ]
        assert combined == singles
        assert combined[0] == [True, False, True, True]
        assert combined[1][4] is False and sum(combined[1]) == 6


class TestSharePool:
    def test_deferred_verdicts_flow(self):
        pub, shares = tpke.deal(4, 2, seed=23)
        svc = tpke.Tpke(pub)
        ct = svc.encrypt(b"pool")
        pool = tpke.SharePool(2)
        for i, s in enumerate(shares[:3]):
            assert pool.add(f"n{i}", svc.dec_share(s, ct))
        assert len(pool) == 3
        assert pool.ready() is None  # nothing verified yet
        senders, shs = pool.collect_pending()
        ok = svc.verify_dec_shares(ct, shs)
        pool.apply_verdicts(senders, ok)
        valid = pool.ready()
        assert valid is not None and len({v.index for v in valid}) >= 2
        # burned sender cannot resubmit after a bad verdict
        pool2 = tpke.SharePool(2)
        bad = tpke.DhShare(1, 2, 3, 4)
        pool2.add("evil", bad)
        s2, sh2 = pool2.collect_pending()
        pool2.apply_verdicts(s2, [False])
        assert not pool2.add("evil", svc.dec_share(shares[0], ct))

    def test_try_verified_compat(self):
        pub, shares = tpke.deal(4, 2, seed=24)
        svc = tpke.Tpke(pub)
        ct = svc.encrypt(b"compat")
        pool = tpke.SharePool(2)
        pool.add("a", svc.dec_share(shares[0], ct))
        assert pool.try_verified(lambda s: svc.verify_dec_shares(ct, s)) is None
        pool.add("b", svc.dec_share(shares[1], ct))
        valid = pool.try_verified(lambda s: svc.verify_dec_shares(ct, s))
        assert valid is not None and len(valid) == 2


class TestHubBatching:
    def test_branch_groups_agree_with_singles(self):
        crypto = BatchCrypto("cpu", 8, 2, 4)
        hub = CryptoHub(crypto)
        rng = np.random.default_rng(31)
        shards = rng.integers(0, 256, size=(3, 8, 64), dtype=np.uint8)
        trees = crypto.merkle.build_batch(shards)
        results = {}

        class Sink:  # bulk-verdict client (the hub's branch contract)
            def on_branch_verdicts(self, ctxs, oks):
                for key, ok in zip(ctxs, oks):
                    results[key] = ok

        sink = Sink()
        wave = HubWave(hub.dedup)
        for t_i, t in enumerate(trees):
            for j in range(8):
                leaf = shards[t_i, j].tobytes()
                if t_i == 1 and j == 3:
                    leaf = b"\xff" + leaf[1:]  # corrupt
                wave.add_branch(
                    sink, t.root, leaf, tuple(t.branch(j)), j, (t_i, j)
                )
        hub._run_branches(*wave.take_branches())
        for t_i, t in enumerate(trees):
            for j in range(8):
                single = crypto.merkle.verify_branch(
                    t.root,
                    shards[t_i, j].tobytes()
                    if (t_i, j) != (1, 3)
                    else b"\xff" + shards[t_i, j].tobytes()[1:],
                    t.branch(j),
                    j,
                )
                assert results[(t_i, j)] == single
        assert results[(1, 3)] is False
        assert sum(results.values()) == 23

    def test_epoch_crypto_goes_through_hub_in_few_dispatches(self):
        """A full N=8 HBBFT epoch: every branch verify, decode and
        share verify rides the hub; total batched dispatches stay far
        below the per-message count (~N^2 branch + ~2N share singles)."""
        from tests.test_honeybadger import (
            assert_identical_batches,
            make_hb_network,
            push_txs,
        )

        cfg, net, nodes = make_hb_network(8, batch_size=16)
        push_txs(nodes, 16)
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        assert_identical_batches(nodes)
        for hb in nodes.values():
            st = hb.hub.stats()
            # the work actually went through the hub...
            # >= n-f echoes/instance: at least one instance's quorum
            assert st["branch_items"] >= 8 * (8 - 2)
            assert st["share_items"] >= 8  # coins + dec shares
            assert st["decode_items"] >= 1
            # ...in batched dispatches, not one per item
            assert st["dispatches"] < st["branch_items"] + st["share_items"]
            assert st["dispatches"] <= 120, st
            # every flush that executed work logged its column width,
            # and the widths account for every item the hub ran
            assert hb.hub.wave_widths
            assert sum(hb.hub.wave_widths) == (
                st["branch_items"] + st["decode_items"] + st["share_items"]
            )


class TestMemoFifo:
    def test_fifo_evicts_oldest_insertion_only(self):
        m = _Memo(4)
        for i in range(4):
            m.put(i, i)
        m.put(4, 4)  # at cap: evicts key 0, keeps everything newer
        assert 0 not in m.map
        assert list(m.map) == [1, 2, 3, 4]
        m.put(2, 22)  # existing key: value refresh, no eviction
        assert m.map[2] == 22 and len(m.map) == 4
        m.put(5, 5)  # next eviction is the NEXT-oldest (1), not all
        assert list(m.map) == [2, 3, 4, 5]


class TestHubWaveIdDedup:
    def test_receiver_copies_collapse_to_one_slot(self):
        """In dedup mode, N clients offering the same decoded-payload
        objects (root/leaf/branch shared via the transport's payload
        memo) produce ONE unique slot; distinct content stays
        distinct even at equal values (identity, not equality)."""
        root, leaf, br = b"r" * 32, b"leaf", (b"s" * 32,)
        wave = HubWave(dedup=True)
        for client in ("a", "b", "c"):
            wave.add_branch(client, root, leaf, br, 1, ctx=client)
        # equal VALUES under different identities must not collapse
        # (bytes(bytearray(..)) forces fresh objects — same-code-object
        # literals would be constant-folded to the very same constant)
        wave.add_branch(
            "d",
            bytes(bytearray(root)),
            bytes(bytearray(leaf)),
            (bytes(bytearray(br[0])),),
            1,
            "d",
        )
        assert len(wave.b_slots) == 2
        assert len(wave.b_items) == 4
        assert [it[2] for it in wave.b_items] == [0, 0, 0, 1]
        # non-dedup mode: every item is its own slot
        wave2 = HubWave(dedup=False)
        wave2.add_branch("a", root, leaf, br, 1, "a")
        wave2.add_branch("b", root, leaf, br, 1, "b")
        assert len(wave2.b_slots) == 2


    def test_frames_dedup_by_payload_and_width_one_by_item(self):
        """A frame record lands whole: receivers sharing one decoded
        payload's tuples share its slots by ONE probe a frame, each
        position made a slot once, whichever receiver first keeps it;
        a width-1 frame (the router wraps a scalar ECHO's fields in
        fresh tuples a delivery) dedups like a per-item append, by the
        item's own objects."""
        import numpy as np

        from cleisthenes_tpu.protocol.echobank import EchoFrame

        roots = tuple(bytes([i]) * 32 for i in range(4))
        branches = tuple((bytes([9]) * 32,) for _ in range(4))
        shards = tuple(b"shard%d" % i for i in range(4))

        def frame(bank, pos, r=roots, b=branches, s=shards):
            pos = np.asarray(pos, dtype=np.int64)
            return EchoFrame(
                bank, 0, 1, pos, pos, pos, pos, r, b, s
            )

        wave = HubWave(dedup=True)
        wave.add_branch_frame(frame("a", [0, 1, 2, 3]))
        wave.add_branch_frame(frame("b", [3, 1]))
        wave.add_branch_frame(frame("c", []))  # nothing kept: no frame
        assert len(wave.b_slots) == 4 and len(wave.b_frames) == 2
        assert wave.b_frame_slots[1].tolist() == [3, 1]
        assert wave.branch_items() == 6
        # a receiver that kept only part of a payload first
        wave = HubWave(dedup=True)
        wave.add_branch_frame(frame("a", [2]))
        wave.add_branch_frame(frame("b", [0, 2]))
        assert wave.b_frame_slots[1].tolist() == [1, 0]
        assert [s[1] for s in wave.b_slots] == [b"shard2", b"shard0"]
        # width 1: fresh wrappers, the same objects inside
        wave = HubWave(dedup=True)
        for bank in ("a", "b"):
            wave.add_branch_frame(
                frame(bank, [0], (roots[0],), (branches[0],), (shards[0],))
            )
        wave.add_branch(object(), roots[0], shards[0], branches[0], 1, None)
        assert len(wave.b_slots) == 1
        # a hub a node: every kept item is its own slot
        wave = HubWave(dedup=False)
        wave.add_branch_frame(frame("a", [0, 1]))
        wave.add_branch_frame(frame("b", [0, 1]))
        assert len(wave.b_slots) == 4
        assert wave.b_frame_slots[1].tolist() == [2, 3]
        slots, items, frames, fslots, clients = wave.take_branches()
        assert (len(slots), items, len(frames), clients) == (4, [], 2, [])
        assert not wave.has_work()


class TestHubLiveness:
    def test_poisoned_share_burn_and_recovery(self):
        """A Byzantine dec-share burns through the batched path and the
        epoch still commits (pool recovers with honest shares)."""
        from tests.test_honeybadger import (
            assert_identical_batches,
            make_hb_network,
            push_txs,
        )
        from cleisthenes_tpu.transport.message import DecSharePayload

        cfg, net, nodes = make_hb_network(4, batch_size=8, seed=3)
        bad = "node2"
        orig_post = net.post

        def tamper(sender_id, receiver_id, msg):
            p = msg.payload
            if sender_id == bad and isinstance(p, DecSharePayload):
                from cleisthenes_tpu.transport.message import Message

                msg = Message(
                    msg.sender_id,
                    msg.timestamp,
                    DecSharePayload(
                        proposer=p.proposer,
                        epoch=p.epoch,
                        index=p.index,
                        d=p.d,
                        e=p.e,
                        z=(p.z + 1),
                    ),
                    msg.signature,
                )
            return orig_post(sender_id, receiver_id, msg)

        net.post = tamper
        push_txs(nodes, 8)
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        assert_identical_batches(nodes)


class _CoinLike:
    """A hub client with BBA's shape: a SharePool whose pending shares
    verify in the share column, whose f+1 verified shares then ride the
    combine column, and whose callback reads the combined value."""

    def __init__(self, hub, pub, base, context, shares, group):
        self.hub, self.pub, self.base, self.context = hub, pub, base, context
        self.group = group
        self.pool = tpke.SharePool(pub.threshold)
        for i, sh in enumerate(shares):
            self.pool.add(f"n{i:03d}", sh)
        self.combined = None
        self.value = None
        self.offered = None
        hub.mark_dirty(self)

    def drain_pending(self, wave):
        # every pooled share, not just need_more(): the roster-size
        # sets then offer more shares than their threshold
        senders, shs = self.pool.collect_pending()
        if senders:
            wave.add_share(
                self.pub, self.base, self.context, senders, shs,
                lambda snd, ok: self.pool.apply_verdicts(snd, ok),
            )

    def offer_combines(self, wave):
        valid = self.pool.ready()
        if valid is not None and self.value is None:
            self.offered = valid
            wave.add_combine(
                valid, self.pub.threshold, self.group,
                lambda val: setattr(self, "combined", val),
            )

    def after_crypto_flush(self):
        if self.combined is not None:
            self.value = self.combined


def _coin_share_sets():
    """Seeded coin share sets at thresholds 2, 6 and 22: (pub, base,
    context, shares) per set — a threshold-size set each, one with
    every share of its roster (larger than its threshold), and the
    t=6 set once more (a repeated set)."""
    sets = []
    for n, t, seed in ((4, 2, 41), (16, 6, 42), (64, 22, 43)):
        pub, secrets = tpke.deal(n, t, seed=seed)
        coin = CommonCoin(pub)
        for k, take in enumerate((t, n)):
            cid = b"combine|%d|%d" % (t, k)
            _pub, base, context = coin.group_params(cid)
            shares = [coin.share(s, cid) for s in secrets[:take]]
            sets.append((pub, base, context, shares))
    sets.append(sets[2])
    return sets


class TestCombineColumn:
    @pytest.mark.parametrize("dedup", [True, False])
    def test_mixed_thresholds_one_dispatch_a_flush_round(self, dedup):
        """Every ready set of a flush round — thresholds 2, 6 and 22
        mixed, one set repeated, three larger than their threshold —
        is combined by ONE exponentiation dispatch, to the values the
        scalar ``combine_shares`` gives."""
        hub = CryptoHub(BatchCrypto("cpu", 4, 1, 2), dedup=dedup)
        sets = _coin_share_sets()
        clients = [
            _CoinLike(hub, pub, base, ctx, shares, pub.group)
            for pub, base, ctx, shares in sets
        ]
        tpke._COMBINE_MEMO.clear()
        hub.flush()
        stats = hub.stats()
        assert stats["combine_items"] == len(sets) == 7
        assert stats["combine_batches"] == 1
        # the repeated set rides its twin's rows
        assert stats["combine_memo_hits"] == 1
        # the combine column stays out of the share-verify dispatches
        assert stats["dispatches"] == 1
        tpke._COMBINE_MEMO.clear()
        assert [len(c.offered) for c in clients] == [2, 4, 6, 16, 22, 64, 6]
        for c in clients:
            assert c.value is not None
            assert c.value == tpke.combine_shares(
                c.offered, c.pub.threshold, c.group
            )
        # a second flush with nothing dirty combines nothing
        hub.flush()
        assert hub.stats()["combine_batches"] == 1

    def test_two_rounds_two_dispatches_and_the_memo(self):
        """One dispatch a flush ROUND: a client whose set completes a
        round later rides that round's dispatch; a set the memo holds
        makes none."""
        hub = CryptoHub(BatchCrypto("cpu", 4, 1, 2), dedup=True)
        sets = _coin_share_sets()
        tpke._COMBINE_MEMO.clear()
        first = [
            _CoinLike(hub, pub, base, ctx, shares, pub.group)
            for pub, base, ctx, shares in sets[:3]
        ]
        hub.flush()
        assert hub.stats()["combine_batches"] == 1
        later = [
            _CoinLike(hub, pub, base, ctx, shares, pub.group)
            for pub, base, ctx, shares in sets[3:]
        ]
        hub.flush()
        stats = hub.stats()
        assert stats["combine_batches"] == 2
        assert stats["combine_items"] == 7
        assert stats["combine_memo_hits"] == 1  # sets[6] is sets[2]
        assert all(c.value is not None for c in first + later)
        assert later[-1].value == first[2].value

    def test_settler_takes_fold_noted_owners_into_one_dispatch(self):
        """take_combines: the first taker's dispatch carries every
        noted owner's ready sets and parks their values; an owner
        whose set moved since combines again."""
        hub = CryptoHub(BatchCrypto("cpu", 4, 1, 2), dedup=True)
        sets = _coin_share_sets()
        tpke._COMBINE_MEMO.clear()

        class Owner:
            def __init__(self, rows):
                self.rows = rows

            def settle_combine_wants(self):
                return list(self.rows)

        def want(k, meta):
            pub, _base, _ctx, shares = sets[k]
            return (meta, shares, pub.threshold, pub.group)

        a = Owner([want(0, "a0"), want(4, "a1")])
        b = Owner([want(2, "b0")])
        c = Owner([want(5, "c0")])
        for o in (a, b, c):
            hub.note_combine_source(o)
        got_a = hub.take_combines(a, a.rows)
        assert hub.stats()["combine_batches"] == 1
        assert hub.stats()["combine_items"] == 4
        # b's pass finds its value parked: no dispatch
        got_b = hub.take_combines(b, b.rows)
        assert hub.stats()["combine_batches"] == 1
        # c's pool moved between the fold and its own pass
        moved = [want(1, "c0")]
        got_c = hub.take_combines(c, moved)
        assert hub.stats()["combine_batches"] == 2
        tpke._COMBINE_MEMO.clear()
        for got, rows in ((got_a, a.rows), (got_b, b.rows), (got_c, moved)):
            for val, (_m, shares, t, group) in zip(got, rows):
                assert val == tpke.combine_shares(shares, t, group)

    def test_forged_dec_share_fails_one_proposer_in_a_batched_pass(self):
        """The settler combines a pass's proposers in one batch and
        tag-checks each result alone: a forged share for ONE proposer
        sends that proposer alone to ``opt_failed`` (and the
        CP-verified path), and the epoch settles the bytes an honest
        run settles."""
        import hashlib

        from cleisthenes_tpu.core.ledger import encode_batch_body
        from cleisthenes_tpu.ops.tpke import DhShare
        from cleisthenes_tpu.protocol.cluster import SimulatedCluster
        from cleisthenes_tpu.protocol.honeybadger import HoneyBadger

        def run(forge: bool):
            cluster = SimulatedCluster(
                config=Config(n=4, batch_size=8, seed=515),
                seed=515,
                key_seed=17,
            )
            bad = cluster.ids[0]  # Shamir index 1: in every subset
            victim = cluster.ids[2]
            hb_bad = cluster.nodes[bad]
            hub = hb_bad.hub
            if forge:
                real_take = hub.take_dec_issues

                def forged_take(owner):
                    rows = real_take(owner)
                    if owner is hb_bad:
                        rows = [
                            (
                                meta,
                                DhShare(s.index, 12345, s.e, s.z)
                                if meta == (0, victim)
                                else s,
                            )
                            for meta, s in rows
                        ]
                    return rows

                hub.take_dec_issues = forged_take
            failed = {}
            real_try = HoneyBadger._try_decrypt

            def spy(self, epoch, es, proposer, kems):
                real_try(self, epoch, es, proposer, kems)
                if es.opt_failed:
                    failed.setdefault(
                        (self.node_id, epoch), set()
                    ).update(es.opt_failed)

            HoneyBadger._try_decrypt = spy
            try:
                for i in range(16):
                    cluster.submit(b"settle-pin-%04d" % i)
                cluster.run_epochs()
            finally:
                HoneyBadger._try_decrypt = real_try
            cluster.assert_agreement()
            h = hashlib.sha256()
            for nid in cluster.ids:
                for epoch, batch in enumerate(
                    cluster.nodes[nid].committed_batches
                ):
                    h.update(encode_batch_body(epoch, batch))
            return h.hexdigest(), failed, hub.stats(), victim

        honest_digest, none_failed, honest_stats, _v = run(forge=False)
        assert not none_failed
        digest, failed, stats, victim = run(forge=True)
        assert digest == honest_digest
        # every validator's epoch-0 pass met the forged share, and it
        # cost the victim's ciphertext alone its optimistic combine
        assert set(failed) == {(nid, 0) for nid in (
            "node000", "node001", "node002", "node003"
        )}
        assert all(props == {victim} for props in failed.values())
        # batched: many sets a dispatch, the verified path's included
        assert stats["combine_items"] > 2 * stats["combine_batches"]
        assert stats["combine_items"] > honest_stats["combine_items"]
