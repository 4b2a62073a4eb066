"""LockstepCluster (protocol.spmd): the synchronous batched executor.

Cross-validates the lockstep path against the full message-passing
cluster (protocol.cluster.SimulatedCluster): same roster, same dealer
keys, same submitted transactions — the committed transaction sets
must be identical, because both run the same protocol with the same
threshold crypto (the combined KEM/coin values are subset-independent,
ops/tpke.py combine docstring)."""

import numpy as np
import pytest

from cleisthenes_tpu.protocol.cluster import SimulatedCluster
from cleisthenes_tpu.protocol.spmd import LockstepCluster


def _tx(i: int) -> bytes:
    return b"spmd-tx-%06d" % i


def _committed_txs(batches) -> set:
    out = set()
    for b in batches:
        out.update(b.tx_list())
    return out


def test_lockstep_commits_all_txs():
    c = LockstepCluster(n=4, batch_size=64, key_seed=3)
    for i in range(128):
        c.submit(_tx(i))
    epochs = c.run_epochs()
    got = _committed_txs(c.committed())
    assert got == {_tx(i) for i in range(128)}
    assert epochs == len(c.committed())
    assert c.pending_tx_count() == 0


def test_lockstep_matches_message_passing_cluster():
    """The flagship equivalence check: lockstep vs full async path."""
    n, batch, total = 4, 64, 256
    lock = LockstepCluster(n=n, batch_size=batch, key_seed=11)
    sim = SimulatedCluster(n=n, batch_size=batch, key_seed=11, seed=5)
    for i in range(total):
        lock.submit(_tx(i))
        sim.submit(_tx(i))
    lock.run_epochs()
    sim.run_epochs()
    lock_txs = _committed_txs(lock.committed())
    sim_txs = _committed_txs(sim.committed("node000"))
    assert lock_txs == sim_txs == {_tx(i) for i in range(total)}


def test_lockstep_epoch_stats_report_real_work():
    c = LockstepCluster(n=4, batch_size=16, key_seed=1)
    for i in range(16):
        c.submit(_tx(i))
    s = c.run_epoch()
    n = 4
    # N^2 decryption-share issues, >= N^2 coin issues (>=1 round)
    assert s["dec_issues"] == n * n
    assert s["coin_issues"] >= n * n
    assert s["bba_rounds"] >= 1
    assert s["epoch_s"] > 0


def test_lockstep_multi_epoch_dedup_and_order():
    """Committed batches dedupe across proposers like the live commit
    rule; epochs drain queues in order."""
    c = LockstepCluster(n=4, batch_size=16, key_seed=2)
    # same tx submitted to two nodes: must commit exactly once
    c.submit(b"dup-tx", node_id=c.ids[0])
    c.submit(b"dup-tx", node_id=c.ids[1])
    c.run_epoch()
    batch = c.committed()[0]
    assert list(batch.tx_list()).count(b"dup-tx") == 1


def test_lockstep_n16_scale():
    c = LockstepCluster(n=16, batch_size=256, key_seed=9)
    for i in range(512):
        c.submit(_tx(i))
    c.run_epochs()
    assert _committed_txs(c.committed()) == {_tx(i) for i in range(512)}


def test_lockstep_conflicting_config_rejected():
    from cleisthenes_tpu.config import Config

    with pytest.raises(ValueError):
        LockstepCluster(n=7, config=Config(n=4, batch_size=16))


def test_lockstep_roster_past_gf256_ceiling():
    """n > 256 forces the GF(2^16) codec inside the full protocol —
    a roster the reference's codec dependency cannot express (256
    total shards).  Kept small-batch; the epoch still runs every
    phase (RS-16 encode/decode, 2^9-leaf Merkle forest, threshold
    coin at f=85, optimistic decryption) for all 257 validators."""
    c = LockstepCluster(n=257, batch_size=257, key_seed=13)
    for i in range(257):
        c.submit(_tx(i))
    c.run_epoch()
    got = _committed_txs(c.committed())
    assert got == {_tx(i) for i in range(257)}
    assert c.crypto.erasure.MAX_N == 1 << 16


def test_lockstep_serial_coin_blocks_match_doubling():
    """The coin_block_doubling knob (the on-chip A/B comparator)
    changes dispatch batching only: committed
    transactions, coin values, and round counts are identical because
    the shares are deterministic VUFs of (epoch, proposer, round)."""
    a = LockstepCluster(n=5, batch_size=40, key_seed=9)
    b = LockstepCluster(
        n=5, batch_size=40, key_seed=9, coin_block_doubling=False
    )
    for i in range(80):
        a.submit(_tx(i))
        b.submit(_tx(i))
    a.run_epochs()
    b.run_epochs()
    assert _committed_txs(a.committed()) == _committed_txs(b.committed())
    assert a.last_stats["bba_rounds"] == b.last_stats["bba_rounds"]
    # serial runs one wave per round; doubling compresses the tail
    assert b.last_stats["coin_waves"] == b.last_stats["bba_rounds"]


def test_lockstep_aggressive_initial_block_matches():
    """coin_block_initial=4 (the RTT-aggressive first block) changes
    dispatch batching only — committed transactions and round counts
    are identical to the default schedule."""
    a = LockstepCluster(n=5, batch_size=40, key_seed=9)
    b = LockstepCluster(
        n=5, batch_size=40, key_seed=9, coin_block_initial=4
    )
    for i in range(80):
        a.submit(_tx(i))
        b.submit(_tx(i))
    a.run_epochs()
    b.run_epochs()
    assert _committed_txs(a.committed()) == _committed_txs(b.committed())
    assert a.last_stats["bba_rounds"] == b.last_stats["bba_rounds"]
    assert b.last_stats["coin_waves"] <= a.last_stats["coin_waves"]


def test_lockstep_reconfig_boundary():
    """Reconfig under the lockstep plane: the activation-boundary swap
    (join + retire + fresh key material) between epochs — committed
    history continuous, every tx exactly once, retiring node's pending
    txs failed over to survivors."""
    c = LockstepCluster(n=4, batch_size=16, key_seed=21)
    for i in range(32):
        c.submit(_tx(i))
    pre_epochs = c.run_epochs()
    pub0 = c.tpke.pub.master
    # strand a tx at the retiring member: it must fail over
    c.submit(_tx(900), node_id="node000")
    c.reconfigure(join=["node100"], retire=["node000"])
    assert c.ids == ["node001", "node002", "node003", "node100"]
    assert c.config.n == 4 and c.config.f == 1
    assert c.tpke.pub.master != pub0  # key material actually rotated
    for i in range(32, 48):
        c.submit(_tx(i))
    c.run_epochs()
    got = _committed_txs(c.committed())
    assert got == {_tx(i) for i in range(48)} | {_tx(900)}
    assert len(c.committed()) > pre_epochs  # epoch counter continuous


def test_lockstep_reduced_quorum_roster():
    """The 2f+1 trust model on the lockstep plane: n=5 carries f=2
    (data shards = n-2f = 1) and still commits everything — the
    quorum-mode seam reaches the batched executor through the same
    Config arithmetic the async plane reads."""
    from cleisthenes_tpu.config import Config

    c = LockstepCluster(
        n=5,
        config=Config(
            n=5, batch_size=16, attested_log=True, reduced_quorum=True
        ),
        key_seed=23,
    )
    assert c.config.f == 2 and c.config.data_shards == 1
    for i in range(20):
        c.submit(_tx(i))
    c.run_epochs()
    assert _committed_txs(c.committed()) == {_tx(i) for i in range(20)}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_lockstep_columnar_waves_match_list_driven(backend, monkeypatch):
    """The executor's waves as byte columns (ShareColumns from issue
    to verify to combine) commit the batches, and take the BBA rounds,
    of the same cluster driven through the list entry points — and
    issue no share as a list, make no DhShare."""
    from cleisthenes_tpu.ops import tpke
    from cleisthenes_tpu.ops.modmath import ModEngine
    from cleisthenes_tpu.protocol import spmd

    if backend == "tpu":  # toy waves sit under the floors: pin the kernels
        monkeypatch.setattr(ModEngine, "host_delegation", False)

    def run():
        c = LockstepCluster(
            n=5, batch_size=40, key_seed=9, crypto_backend=backend
        )
        for i in range(80):
            c.submit(_tx(i))
        rounds = []
        while c.pending_tx_count():
            rounds.append(c.run_epoch()["bba_rounds"])
        return [b.contributions for b in c.committed()], rounds

    tpke.reset_share_tally()
    columnar = run()
    tally = tpke.share_tally()
    assert tally["shares_issued_listed"] == 0
    assert tally["shares_materialized"] == 0
    assert tally["shares_issued_columnar"] > 0

    def issue_listed(waves, group, backend, mesh):
        return tpke.issue_shares_batch(
            [
                (sec, base, context, vk)
                for w in waves
                for base, context in w.pairs
                for sec, vk in zip(w.secrets, w.vks)
            ],
            group=group, backend=backend, mesh=mesh,
        )

    monkeypatch.setattr(spmd, "issue_share_columns", issue_listed)
    tpke.reset_share_tally()
    listed = run()
    tally = tpke.share_tally()
    assert tally["shares_issued_columnar"] == 0
    assert tally["shares_issued_listed"] == tally["shares_materialized"] > 0
    assert columnar == listed
    assert len(columnar[1]) >= 2 and all(r >= 1 for r in columnar[1])
