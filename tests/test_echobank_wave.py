"""EchoBank's wave entries against the per-payload entries (ISSUE 35).

A delivery wave's ECHOes and READYs run as ONE vectorized pass through
``EchoBank.wave_echo`` / ``wave_ready``, park as one frame a sender's
payload, cross the hub's branch column whole and take their verdicts
back as boolean arrays.  ``RBC.handle_echo_fast`` /
``handle_ready_root`` remain as the per-payload entries and write the
same state.  These tests drive twin ACS states — one through the wave
entries, one item by item — with the same waves (replays, delivered
instances, strangers, malformed shapes, an equivocating proposer,
rows that repeat an instance, failing proofs, a verified shard of
another length, the two entries interleaved) and hold them to the
same bank arrays, the same branch work offered to the hub, the same
verdict effects, READY emissions and deliveries, in order; and one
seeded honest epoch holds the wave path's call shape, so a per-item
pass that creeps back fails here and not on the chip.
"""

from __future__ import annotations

import functools
import hashlib
import random

import numpy as np
import pytest

from cleisthenes_tpu.config import Config
from cleisthenes_tpu.ops import tpke
from cleisthenes_tpu.ops.backend import get_backend
from cleisthenes_tpu.ops.coin import CommonCoin
from cleisthenes_tpu.ops.merkle import _EMPTY_LEAF_DIGEST
from cleisthenes_tpu.ops.payload import split_payload
from cleisthenes_tpu.protocol.acs import ACS
from cleisthenes_tpu.protocol.cluster import SimulatedCluster
from cleisthenes_tpu.protocol.hub import CryptoHub
from cleisthenes_tpu.transport.message import RbcPayload, RbcType
from cleisthenes_tpu.utils.metrics import Metrics


def _sha(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def _tree(leaves, n):
    """(root, [branch of leaf j]) of the padded tree over ``leaves``,
    by hand — leaves may differ in length, which the library's matrix
    builder cannot express and a Byzantine proposer can."""
    width = 1
    while width < n:
        width <<= 1
    level = [_sha(b"\x00" + leaf) for leaf in leaves]
    level += [_EMPTY_LEAF_DIGEST] * (width - len(level))
    levels = [level]
    while len(level) > 1:
        level = [
            _sha(b"\x01" + level[i] + level[i + 1])
            for i in range(0, len(level), 2)
        ]
        levels.append(level)
    branches = []
    for j in range(n):
        idx, path = j, []
        for lvl in levels[:-1]:
            path.append(lvl[idx ^ 1])
            idx >>= 1
        branches.append(tuple(path))
    return levels[-1][0], branches


class _Material:
    """Per roster size: keys, and for every proposer its honest tree,
    a second tree (what an equivocating proposer shows other
    receivers) and a third whose odd leaves are 8 bytes longer (valid
    proofs of two lengths under one root)."""

    def __init__(self, n: int) -> None:
        self.cfg = Config(n=n)
        self.crypto = get_backend(self.cfg)
        self.ids = [f"node{i:03d}" for i in range(n)]
        pub, self.secrets = tpke.deal(n, self.cfg.f + 1, seed=21)
        self.coin = CommonCoin(pub)
        self.trees = {}
        for p_i, p in enumerate(self.ids):
            variants = []
            for v in range(2):
                value = _sha(b"%d-%d" % (p_i, v)) * (3 + v)
                shards = self.crypto.erasure.encode(
                    split_payload(value, self.cfg.data_shards)
                )
                leaves = [shards[j].tobytes() for j in range(n)]
                variants.append((leaves,) + _tree(leaves, n))
            leaves = [
                leaf + (b"\x77" * 8 if j % 2 else b"")
                for j, leaf in enumerate(variants[0][0])
            ]
            variants.append((leaves,) + _tree(leaves, n))
            self.trees[p] = variants

    def echo(self, proposer: str, sender_index: int, variant: int = 0):
        leaves, root, branches = self.trees[proposer][variant]
        return root, branches[sender_index], leaves[sender_index]


@functools.lru_cache(maxsize=None)
def _material(n: int) -> _Material:
    return _Material(n)


class _Out:
    """What the ACS sends, in order."""

    def __init__(self) -> None:
        self.sent = []

    def broadcast(self, payload) -> None:
        self.sent.append(payload)

    def send_to(self, member, payload) -> None:
        self.sent.append((member, payload))


class _Twin:
    """One ACS on a deferred hub of its own, its branch work and its
    deliveries recorded."""

    def __init__(self, mat: _Material, dedup: bool) -> None:
        self.mat = mat
        self.out = _Out()
        self.metrics = Metrics()
        self.hub = CryptoHub(mat.crypto, dedup=dedup)
        self.hub.defer = True
        self.acs = ACS(
            config=mat.cfg,
            crypto=mat.crypto,
            epoch=0,
            owner=mat.ids[0],
            member_ids=mat.ids,
            coin=mat.coin,
            coin_secret=mat.secrets[0],
            out=self.out,
            hub=self.hub,
            metrics=self.metrics,
        )
        self.branch_work = []
        self.delivered = []
        verify = self.hub._verify_branch_groups

        def recording(items, deliver):
            self.branch_work.extend(tuple(it[:4]) for it in items)
            verify(items, deliver)

        self.hub._verify_branch_groups = recording
        for rbc in self.acs.rbcs.values():
            inner = rbc.on_deliver

            def on_deliver(proposer, value, inner=inner):
                self.delivered.append(proposer)
                inner(proposer, value)

            rbc.on_deliver = on_deliver

    # -- the two ways in ---------------------------------------------------

    def echo_items(self, rows) -> None:
        """Item by item, as the bank's filters order them: strangers,
        then delivered instances, then RBC's scalar entry."""
        rbcs = self.acs.rbcs
        for sender, shard_index, proposers, roots, branches, shards in rows:
            for k, proposer in enumerate(proposers):
                rbc = rbcs.get(proposer)
                if rbc is not None and not rbc.delivered:
                    rbc.handle_echo_fast(
                        sender, roots[k], branches[k], shards[k], shard_index
                    )

    def ready_items(self, rows) -> None:
        rbcs = self.acs.rbcs
        for sender, proposers, roots in rows:
            for k, proposer in enumerate(proposers):
                rbc = rbcs.get(proposer)
                if rbc is not None:
                    rbc.handle_ready_root(sender, roots[k])

    def step(self, kind: str, rows, mode: str) -> None:
        wave, items = (
            (self.acs.handle_echo_wave, self.echo_items)
            if kind == "echo"
            else (self.acs.handle_ready_wave, self.ready_items)
        )
        if mode == "wave":
            wave(rows)
        elif mode == "items":
            items(rows)
        else:  # the two entries interleaved inside one wave
            cut = len(rows) // 2
            wave(rows[:cut])
            items(rows[cut : cut + 1])
            wave(rows[cut + 1 :])

    # -- what must agree ---------------------------------------------------

    def snapshot(self) -> dict:
        bank = self.acs.echo_bank
        live = bank.state == 0
        rows = len(bank._row_roots)
        # a halted instance's columns are dead state: the wave pass
        # lands a wave's adds before it fires the delivery that halts
        # the instance, the per-item path stops counting at that item
        snap = {
            "state": bank.state.tolist(),
            "registry": list(bank._root_rows.items()),
            "primary_row": bank.primary_row[live].tolist(),
            "has_parked": bank.has_parked.tolist(),
            "quorum_row": bank.quorum_row[live].tolist(),
            "flush_wanted": self.hub.flush_wanted,
            "readies": [
                (p.proposer, p.root_hash)
                for p in self.out.sent
                if isinstance(p, RbcPayload) and p.type == RbcType.READY
            ],
            "delivered": list(self.delivered),
            "branch_work": list(self.branch_work),
            "rbcs": [
                (
                    rbc._ready_root,
                    rbc._value,
                    sorted(rbc._decode_req),
                    sorted(rbc._decoded),
                    sorted(rbc._bad_roots),
                )
                for rbc in self.acs.rbcs.values()
            ],
        }
        for name in ("echo_seen", "ready_seen", "ver_row"):
            snap[name] = getattr(bank, name)[:, live].tolist()
        for name in ("echo_pot", "echo_ok", "ready_cnt", "shard_len"):
            snap[name] = getattr(bank, name)[:rows][:, live].tolist()
        return snap


def _assert_same(a: _Twin, b: _Twin, where: str) -> None:
    sa, sb = a.snapshot(), b.snapshot()
    for key in sa:
        assert sa[key] == sb[key], f"{where}: {key} differs"


def _payload(mat, sender_index, proposers, variant=0, mutate=None):
    """One sender's ECHO row over ``proposers`` (ids; strangers get the
    first member's proof)."""
    roots, branches, shards = [], [], []
    for k, proposer in enumerate(proposers):
        known = proposer if proposer in mat.trees else mat.ids[0]
        v = variant(k, proposer) if callable(variant) else variant
        root, branch, shard = mat.echo(known, sender_index, v)
        if mutate is not None:
            root, branch, shard = mutate(k, root, branch, shard)
        roots.append(root)
        branches.append(branch)
        shards.append(shard)
    return (
        mat.ids[sender_index], sender_index, tuple(proposers),
        tuple(roots), tuple(branches), tuple(shards),
    )


def _ready_row(mat, sender_index, proposers, variant=0):
    return (
        mat.ids[sender_index],
        tuple(proposers),
        tuple(
            mat.trees[p if p in mat.trees else mat.ids[0]][variant][1]
            for p in proposers
        ),
    )


def _script(mat: _Material):
    """The named cases, as (kind, rows) steps ('flush' runs the hub).

    The last member is the Byzantine one: as a sender it ships
    malformed rows, failing proofs and a row that repeats an instance;
    as a proposer it equivocates (odd senders echo its second root).
    The second member's tree has leaves of two lengths, so its
    verified echoes conflict: the first verified length stands."""
    n, f, ids = mat.cfg.n, mat.cfg.f, mat.ids
    everyone = tuple(ids)
    half = everyone[: n // 2]
    bad = n - 1
    quorum = n - f

    def variant(j):
        def pick(k, proposer):
            if proposer == ids[bad]:
                return j % 2
            return 2 if proposer == ids[1] else 0

        return pick

    def honest(j, proposers=everyone):
        return _payload(mat, j, proposers, variant=variant(j))

    def corrupt(k, root, branch, shard):
        return root, branch, bytes([shard[0] ^ 1]) + shard[1:]

    def malformed(k, root, branch, shard):
        return (
            (root[:31], branch, shard),
            (root, branch[:-1], shard),
            (root, branch[:-1] + (branch[-1][:16],), shard),
            (root, branch, b""),
        )[k % 4]

    root0 = {p: mat.trees[p][0][1] for p in ids}
    return [
        # a few senders over half the instances, a stranger, a frame
        # replayed inside the wave, an unknown proposer inside a row,
        # malformed shapes (which claim nothing)
        ("echo", [
            honest(0, half),
            honest(1, half),
            ("stranger",) + honest(2, half)[1:],
            honest(1, half),
            honest(2, half[:1] + ("ghost",) + half[1:]),
            _payload(mat, bad, everyone, mutate=malformed),
        ]),
        ("flush", None),
        # the Byzantine sender: failing proofs (burned slots), and a
        # row that repeats an instance under two roots
        ("echo", [
            _payload(mat, bad, half, mutate=corrupt),
            _payload(
                mat, bad, (ids[n // 2], ids[n // 2]),
                variant=lambda k, p: k,
            ),
        ]),
        # N-f senders over every instance: their first half replays
        ("echo", [honest(j) for j in range(quorum)]),
        ("flush", None),
        # READYs: f+1 rows (the relay, where no echo quorum sent one),
        # a replay, a stranger, a malformed root beside an unknown
        # proposer, a row repeating an instance under two roots
        ("ready", [_ready_row(mat, j, everyone) for j in range(f + 1)] + [
            _ready_row(mat, 0, everyone),
            ("stranger", everyone, _ready_row(mat, 1, everyone)[2]),
            (
                ids[f + 1], (ids[0], "ghost", ids[2]),
                (b"short", root0[ids[0]], root0[ids[2]]),
            ),
            (
                ids[bad], (ids[2], ids[2]),
                (root0[ids[2]], mat.trees[ids[2]][1][1]),
            ),
        ]),
        ("flush", None),
        # the other senders' echoes, then the READY quorum: deliveries
        # inside the wave, and a decode asked for by READYs alone
        # (the echoes still parked when their instance delivers leave
        # their frames at the drain)
        ("echo", [honest(j) for j in range(quorum, n - 1)] + [
            _payload(mat, 0, ("ghost", "wraith")),
        ]),
        ("ready", [
            _ready_row(mat, j, everyone) for j in range(f + 1, n - 1)
        ]),
        ("flush", None),
        # late traffic for delivered instances, the Byzantine READY
        ("echo", [honest(1), honest(bad)]),
        ("ready", [_ready_row(mat, bad, everyone, variant=1)]),
        ("flush", None),
    ]


def _run(mat, steps, mode: str, dedup: bool):
    wave = _Twin(mat, dedup)
    items = _Twin(mat, dedup)
    # replays the wave pass absorbed for an instance that a delivery
    # INSIDE that wave halted: item by item they arrive after the
    # halt and drop uncounted (as VoteBank._wave_apply's do)
    extra = 0
    for i, (kind, rows) in enumerate(steps):
        if kind == "flush":
            wave.hub.flush()
            items.hub.flush()
        else:
            before = len(items.delivered)
            wave.step(kind, rows, mode)
            items.step(kind, rows, "items")
            if len(items.delivered) != before:
                more = (
                    wave.metrics.dedup_absorbed.value
                    - items.metrics.dedup_absorbed.value
                )
                assert more >= extra
                extra = more
        _assert_same(wave, items, f"step {i} ({kind})")
        assert (
            wave.metrics.dedup_absorbed.value
            == items.metrics.dedup_absorbed.value + extra
        ), f"step {i} ({kind}): absorbed differs"
    return wave, items


@pytest.mark.parametrize("dedup", [False, True], ids=["hub-a-node", "shared"])
@pytest.mark.parametrize("mode", ["wave", "mixed"])
@pytest.mark.parametrize("n", [4, 16, 64])
def test_wave_entry_matches_item_by_item(n, mode, dedup):
    mat = _material(n)
    wave, items = _run(mat, _script(mat), mode, dedup)
    # the script exercised what it names: burned claims, a verified
    # shard of a conflicting length dropped, deliveries, READYs sent
    bank = wave.acs.echo_bank
    assert wave.delivered, "no instance delivered"
    assert wave.snapshot()["readies"], "no READY emitted"
    assert len(bank._row_roots) > n, "no second root registered"
    assert wave.metrics.dedup_absorbed.value > 0
    if mode == "wave":
        # rows that repeat an instance are the scalar rest
        assert wave.metrics.echo_items_scalar.value == 1
        assert wave.metrics.echo_items_wave.value > 0
    assert items.metrics.echo_items_wave.value == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("n", [4, 16, 64])
def test_random_waves_match_item_by_item(n, seed):
    """Random waves: every row a random subset of proposers in random
    order, every item honest, equivocated, corrupted, malformed or of
    the other length at random; senders repeat, strangers appear, rows
    repeat instances; READY waves and flushes in between."""
    mat = _material(n)
    rng = random.Random(1000 * n + seed)
    ids = mat.ids

    def echo_row():
        j = rng.randrange(n)
        width = rng.choice((1, 2, n // 2, n, n))
        proposers = rng.sample(ids, width)
        if rng.random() < 0.1:
            proposers.insert(rng.randrange(width + 1), "ghost")
        if rng.random() < 0.08:
            proposers.append(proposers[0])
        kinds = [
            rng.choices(range(6), (70, 8, 6, 6, 6, 4))[0] for _ in proposers
        ]

        def mutate(k, root, branch, shard):
            kind = kinds[k]
            if kind == 3:
                return root, branch, bytes([shard[0] ^ 1]) + shard[1:]
            if kind == 4:
                return root[:-1], branch, shard
            if kind == 5:
                return root, branch + (branch[0],), shard
            return root, branch, shard

        row = _payload(
            mat, j, proposers,
            variant=lambda k, p: kinds[k] if kinds[k] < 3 else 0,
            mutate=mutate,
        )
        if rng.random() < 0.05:
            row = ("stranger",) + row[1:]
        if rng.random() < 0.03:
            row = row[:1] + (n,) + row[2:]  # shard index out of range
        return row

    def ready_row():
        j = rng.randrange(n)
        proposers = rng.sample(ids, rng.choice((1, n // 2, n, n)))
        if rng.random() < 0.08:
            proposers.append(proposers[0])
        roots = tuple(
            mat.trees[p][0 if rng.random() < 0.9 else 1][1]
            if rng.random() < 0.97
            else b"bad"
            for p in proposers
        )
        return ids[j], tuple(proposers), roots

    steps = []
    for _ in range(10):
        steps.append(
            ("echo", [echo_row() for _ in range(rng.randrange(1, n + 1))])
        )
        if rng.random() < 0.6:
            steps.append(
                ("ready", [ready_row() for _ in range(rng.randrange(1, n))])
            )
        if rng.random() < 0.7:
            steps.append(("flush", None))
    steps.append(("flush", None))
    _run(mat, steps, "wave", dedup=bool(seed % 2))


def test_verdicts_shared_across_receivers_keep_banks_apart():
    """Two receivers on one dedup hub take the same payload objects:
    one slot a distinct proof, each bank its own verdict arrays."""
    mat = _material(4)
    hub = CryptoHub(mat.crypto, dedup=True)
    hub.defer = True
    twins = []
    for owner in range(2):
        t = _Twin.__new__(_Twin)
        t.acs = ACS(
            config=mat.cfg, crypto=mat.crypto, epoch=0,
            owner=mat.ids[owner], member_ids=mat.ids, coin=mat.coin,
            coin_secret=mat.secrets[owner], out=_Out(), hub=hub,
        )
        twins.append(t)
    rows = [_payload(mat, j, tuple(mat.ids)) for j in range(3)]
    for t in twins:
        t.acs.handle_echo_wave(rows)
    hub.flush()
    st = hub.stats()
    assert st["branch_frames"] == 6
    assert st["branch_items"] == 24
    assert st["branch_slots"] == 12
    for t in twins:
        bank = t.acs.echo_bank
        assert bank.echo_ok[:4].sum() == 12
        assert (bank.ver_row[:3] >= 0).all() and (bank.ver_row[3] < 0).all()


def test_honest_epoch_call_shape():
    """One seeded honest epoch at N=16 on the shared hub: every ECHO
    item claims through the wave pass, a frame is a (sender, receiver)
    pair's payload, and the hub's id-dedup leaves N^2 distinct proofs
    — a per-item pass that creeps back moves one of these."""
    n = 16
    cluster = SimulatedCluster(
        config=Config(n=n, batch_size=64, seed=35), seed=35, key_seed=5
    )
    for i in range(64):
        cluster.submit(b"shape-%04d" % i)
    cluster.run_epochs()
    depth = cluster.assert_agreement()
    metrics = [cluster.nodes[nid].metrics for nid in cluster.ids]
    wave = sum(m.echo_items_wave.value for m in metrics)
    scalar = sum(m.echo_items_scalar.value for m in metrics)
    hub = cluster.nodes[cluster.ids[0]].hub.stats()
    assert scalar == 0
    assert 0 < hub["branch_items"] <= wave <= depth * n ** 3
    # k = N - 2f verified shards is the least a delivery needs
    assert hub["branch_items"] >= depth * n * n * (n - 2 * cluster.config.f)
    assert hub["branch_frames"] <= depth * n * n
    assert hub["branch_slots"] == depth * n * n
    snap = metrics[0].snapshot()["banks"]
    assert snap == {
        "echo_items_wave": metrics[0].echo_items_wave.value,
        "echo_items_scalar": 0,
    }
