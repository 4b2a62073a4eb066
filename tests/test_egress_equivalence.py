"""The columnar egress plane (ISSUE 13).

Outbound work runs at wave granularity: one coalescer flush hands its
whole wave of folded bundles to ONE ``Authenticator.sign_wire_wave``
pass (payload bodies encode once per distinct object through the
shared-prefix ``FrameEncodeMemo``, MACs batch over the PR-7
precomputed key schedules), single-receiver sends ride the same
signer, and the protocol plane's pending coin-share issues pool in the
CryptoHub's coin column — one native multi-exponentiation dispatch per
staged pool per wave.  These tests hold the wave signer byte-identical
to looping ``sign_wire_many``, the batched coin kernels to their
per-item maps, and run the PR-4 semantic coalitions, which still lie
per-receiver through the wave flush.  What a seeded run COMMITS and
puts on the wire is pinned in tests/test_seeded_pins.py.
"""

from __future__ import annotations

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from cleisthenes_tpu.config import Config  # noqa: E402
from cleisthenes_tpu.protocol.cluster import SimulatedCluster  # noqa: E402


# ---------------------------------------------------------------------------
# codec/signer-level parity: sign_wire_wave vs sign_wire_many
# ---------------------------------------------------------------------------


def test_sign_wire_wave_parity_and_memo_sharing():
    """The wave signer must produce byte-identical frames to looping
    sign_wire_many (the gRPC egress path's signer — this IS the
    wire-frame equivalence proof at the seam real sockets use), share
    payload-body encodes across a wave's bundles via the memo, and
    evict FIFO."""
    from cleisthenes_tpu.transport.base import (
        HmacAuthenticator,
        NullAuthenticator,
    )
    from cleisthenes_tpu.transport.message import (
        BbaPayload,
        BbaType,
        BundlePayload,
        FrameEncodeMemo,
        Message,
        RbcPayload,
        RbcType,
    )

    roster = ["node0", "node1", "node2", "node3"]
    auth = HmacAuthenticator.derive(b"egress-master", "node0", roster)
    shared = BbaPayload(BbaType.BVAL, "node0", 3, 1, True)
    vals = [
        RbcPayload(
            RbcType.VAL, "node0", 3, b"r" * 32, (b"b" * 32,),
            shard_index=i, shard=b"s%d" % i,
        )
        for i in range(3)
    ]
    # a mixed egress wave: per-receiver bundles sharing one broadcast
    # run object (`shared`) plus a distinct VAL each — the coalescer's
    # exact output shape
    msgs = [
        Message(
            sender_id="node0",
            timestamp=99.25,
            payload=BundlePayload((shared, vals[i])),
        )
        for i in range(3)
    ]
    items = [(m, [f"node{i + 1}"]) for i, m in enumerate(msgs)]
    memo = FrameEncodeMemo()
    waved = auth.sign_wire_wave(items, memo)
    for (m, rids), frames in zip(items, waved):
        want = auth.sign_wire_many(m, rids)
        assert frames == want, "wave signer drifted from scalar signer"
    # `shared` encoded once, hit twice; each VAL encoded once
    assert memo.hits == 2 and memo.misses == 4, (memo.hits, memo.misses)
    # Null backend parity (benchmarks isolating crypto cost)
    null = NullAuthenticator()
    nw = null.sign_wire_wave(items, FrameEncodeMemo())
    for (m, rids), frames in zip(items, nw):
        assert frames == null.sign_wire_many(m, rids)
    # FIFO eviction: at cap the OLDEST entry goes, never the table
    small = FrameEncodeMemo(cap=2)
    from cleisthenes_tpu.transport.message import encode_payload_shared

    for p in (shared, vals[0], vals[1]):
        encode_payload_shared(p, small)
    assert len(small.map) == 2
    encode_payload_shared(vals[1], small)  # newest still resident
    assert small.hits == 1


# ---------------------------------------------------------------------------
# ops-level parity: the wave-batched coin kernels vs their scalar maps
# ---------------------------------------------------------------------------


def test_coin_share_batch_matches_scalar_kernels():
    """`CommonCoin.share_batch` / `verify_shares_batch` are the
    coin-only batch entry points for callers without a hub (lockstep
    executor, tests) — the batch results must match mapping the
    scalar `share` / `verify_shares` kernels item for item, and a
    tampered share must fail exactly where the scalar check fails."""
    from cleisthenes_tpu.ops import tpke
    from cleisthenes_tpu.ops.coin import CommonCoin

    pub, secrets = tpke.deal(4, 2, seed=23)
    coin = CommonCoin(pub)
    coin_ids = [b"egr-coin-%d" % r for r in range(3)]
    sec = secrets[1]
    batch = coin.share_batch(sec, coin_ids)
    assert len(batch) == 3
    per_coin = []
    for cid, sh in zip(coin_ids, batch):
        # a batch-issued share verifies under the scalar verifier...
        assert coin.verify_shares(cid, [sh]) == [True]
        # ...and combines to the same deterministic VUF value as a
        # quorum of scalar-issued shares
        others = [coin.share(secrets[0], cid), coin.share(secrets[2], cid)]
        assert coin.toss(cid, others) == coin.toss(cid, [sh, others[0]])
        per_coin.append((cid, [sh] + others))
    # batched verify across every coin == mapping verify_shares
    verdicts = coin.verify_shares_batch(per_coin)
    assert verdicts == [
        coin.verify_shares(cid, shs) for cid, shs in per_coin
    ]
    assert all(all(v) for v in verdicts)
    # a forged share fails in the batch exactly like in the scalar map
    from cleisthenes_tpu.ops.tpke import DhShare

    good = per_coin[1][1][0]
    forged = DhShare(good.index, good.d + 1, good.e, good.z)
    tampered = [
        (per_coin[0][0], per_coin[0][1]),
        (per_coin[1][0], [forged] + per_coin[1][1][1:]),
    ]
    got = coin.verify_shares_batch(tampered)
    assert got[0] == [True, True, True]
    assert got[1][0] is False and got[1][1:] == [True, True]
    assert coin.share_batch(sec, []) == []
    assert coin.verify_shares_batch([]) == []


# ---------------------------------------------------------------------------
# PR-4 semantic coalitions against the columnar egress plane
# ---------------------------------------------------------------------------


def _drive_coalition(behaviors: dict, n: int, seed: int):
    """Run a Byzantine coalition; returns
    (agreed honest depth, the network) — assert_agreement = identical
    ledger prefixes."""
    bad = sorted(behaviors)
    cluster = SimulatedCluster(
        n=n,
        config=Config(n=n, batch_size=8),
        seed=seed,
        key_seed=27,
        behaviors=behaviors,
    )
    honest = [i for i in cluster.ids if i not in bad]
    for i in range(12):
        cluster.submit(b"tx-%04d" % i, node_id=honest[i % len(honest)])
    cluster.run_until_drained(max_rounds=30, skip=bad)
    depth = cluster.assert_agreement(skip=bad)
    for nid in honest:
        for batch in cluster.nodes[nid].committed_batches:
            for tx in batch.tx_list():
                assert tx.startswith(b"tx-"), tx
    return depth, cluster.net


@pytest.mark.faults
def test_equivocator_coalition_columnar_egress():
    """An Equivocator's per-receiver lies enter BETWEEN the protocol
    plane and the coalescer, so the columnar flush must sign each
    receiver's distinct bundle separately (per-receiver signable)
    while the honest run's shared bodies still fold through the
    memo — conflating the two would either leak one receiver's lie to
    another or fail the MACs wholesale."""
    from cleisthenes_tpu.protocol.byzantine import make_behavior

    behaviors = {"node003": make_behavior("equivocator", seed=51)}
    depth, net = _drive_coalition(behaviors, n=4, seed=37)
    assert depth >= 1
    assert behaviors["node003"].rewrites > 0, "adversary never lied"
    # the liar's per-receiver fan-out makes mixed egress waves whose
    # unrewritten payload objects are shared across receivers — the
    # encode memo must actually dedup them
    stats = net.delivery_stats()
    assert stats["encode_memo_hits"] > 0, stats


@pytest.mark.faults
def test_selective_mute_coalition_columnar_egress():
    """SelectiveMute silences chosen links: the muted receivers'
    entries simply vanish from the egress wave, and the remaining
    per-receiver frames must still sign and deliver (honest quorums
    reach agreement without the starved links)."""
    from cleisthenes_tpu.protocol.byzantine import make_behavior

    behaviors = {"node003": make_behavior("selective_mute", seed=52)}
    depth, _net = _drive_coalition(behaviors, n=4, seed=41)
    assert depth >= 1
