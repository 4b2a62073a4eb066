"""The crypto-group seam, demonstrated (VERDICT round-2 item 6).

ops/tpke.py's security notes promise the modulus is a seam: "a
production deployment would swap the group seam for a pairing curve or
a larger prime — the API and the batched-verify data flow are
unchanged".  These tests run the full threshold stack — TPKE.SetUp /
Encrypt / DecShare / batched CP verify / Decrypt
(reference docs/THRESHOLD_ENCRYPTION-EN.md:33-36) plus the common coin
(docs/BBA-EN.md:163-181) — under NON-default groups:

- a second 256-bit safe prime, through BOTH engines (the native C++
  Montgomery kernel and the XLA limb kernel: one compiled program
  serves every <=256-bit group, constants ride in as traced arrays);
- the 2048-bit RFC 3526 MODP-14 safe prime, CPU-only, proving the
  limb-free python path and every byte-width in the CP transcripts
  generalize past the 256-bit layout.
"""

import pytest

from cleisthenes_tpu.ops import tpke
from cleisthenes_tpu.ops.coin import CommonCoin
from cleisthenes_tpu.ops.modmath import DEFAULT_GROUP, GroupParams, get_engine

# Second 256-bit safe prime (deterministic search, seed 20260730,
# 64-round Miller-Rabin), g = 4 generates the order-q QR subgroup.
P2 = 0x93A40B764F1F5026ADA7C38AA3EF4EE81E01E89F9FE80837B1E370913DA99F13
GROUP2 = GroupParams(p=P2, q=(P2 - 1) // 2, g=4)

# RFC 3526 group 14: 2048-bit MODP safe prime (well-known constant).
MODP14 = int(
    "FFFFFFFFFFFFFFFFC90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74"
    "020BBEA63B139B22514A08798E3404DDEF9519B3CD3A431B302B0A6DF25F1437"
    "4FE1356D6D51C245E485B576625E7EC6F44C42E9A637ED6B0BFF5CB6F406B7ED"
    "EE386BFB5A899FA5AE9F24117C4B1FE649286651ECE45B3DC2007CB8A163BF05"
    "98DA48361C55D39A69163FA8FD24CF5F83655D23DCA3AD961C62F356208552BB"
    "9ED529077096966D670C354E4ABC9804F1746C08CA18217C32905E462E36CE3B"
    "E39E772C180E86039B2783A2EC07A28FB5C55DF06F4C52C9DE2BCBF695581718"
    "3995497CEA956AE515D2261898FA051015728E5A8AACAA68FFFFFFFFFFFFFFFF",
    16,
)
GROUP14 = GroupParams(p=MODP14, q=(MODP14 - 1) // 2, g=4)

N, F = 7, 2


def _roundtrip(group: GroupParams, engine_backend: str) -> None:
    """Full threshold-decryption + coin lifecycle under ``group``."""
    pub, shares = tpke.deal(N, F + 1, seed=9, group=group)
    assert pub.group is group

    # subgroup membership sanity in this group
    assert tpke.is_group_element(pub.master, group)
    assert not tpke.is_group_element(group.p - 1, group)  # order-2 elt

    svc = tpke.Tpke(pub, backend=engine_backend)
    msg = b"the woods are lovely, dark and deep" * 3
    ct = svc.encrypt(msg)
    assert tpke.is_group_element(ct.c1, group)

    dec = [svc.dec_share(shares[i], ct) for i in range(N)]
    ok = tpke.verify_shares(
        pub, ct.c1, dec, svc.context(ct), backend=engine_backend
    )
    assert all(ok)
    # a corrupted share must fail CP verification in this group too
    bad = tpke.DhShare(index=dec[0].index, d=dec[0].d, e=dec[0].e,
                       z=(dec[0].z + 1) % group.q)
    assert tpke.verify_shares(
        pub, ct.c1, [bad], svc.context(ct), backend=engine_backend
    ) == [False]

    # any f+1 subset decrypts identically
    assert svc.combine(ct, dec[: F + 1]) == msg
    assert svc.combine(ct, dec[F + 1 :]) == msg

    # the common coin over the same group: identical bit from any
    # threshold subset, shares verifiable
    cpub, cshares = tpke.deal(N, F + 1, seed=10, group=group)
    coin = CommonCoin(cpub, backend=engine_backend)
    cid = b"epoch|instance|round0"
    cs = [coin.share(cshares[i], cid) for i in range(N)]
    assert all(coin.verify_shares(cid, cs))
    bits = {coin.toss(cid, subset) for subset in (cs[: F + 1], cs[F + 1 :])}
    assert len(bits) == 1


def test_second_256bit_prime_cpu_engine():
    _roundtrip(GROUP2, "cpu")


def test_second_256bit_prime_xla_engine(jax_cpu_devices):
    _roundtrip(GROUP2, "tpu")


def test_2048bit_modp14_cpu_only():
    _roundtrip(GROUP14, "cpu")


# The packaged 384-bit safe-prime group (BLS12-381 base-field width
# class, (12, 32) XLA limb family) — see ops/modmath.GROUP384.
from cleisthenes_tpu.ops.modmath import GROUP384, P384  # noqa: E402

# The measured per-family floors (ModEngine.WIDE_FLOORS) delegate
# small wide-group batches to the host — so device-path correctness
# tests pin the device kernels with host_delegation=False (the
# class-level test escape; round-4 review found the earlier version
# comparing python pow against python pow).
WIDE_BATCH = 24


@pytest.fixture
def device_pinned(monkeypatch):
    from cleisthenes_tpu.ops.modmath import ModEngine

    monkeypatch.setattr(ModEngine, "host_delegation", False)


def test_384bit_group_xla_engine_matches_pow(
    jax_cpu_devices, device_pinned
):
    """The wide XLA limb family (SURVEY §7 hard part 1: a group sized
    for BLS12-381's base field on the device path, replacing round-3's
    256-bit rejection)."""
    import random

    rng = random.Random(7)
    eng = get_engine("tpu", group=GROUP384)
    assert eng._host_floor(WIDE_BATCH) is None  # really the device path
    bases = [rng.randrange(2, P384) for _ in range(2 * WIDE_BATCH)]
    exps = [rng.randrange(1, GROUP384.q) for _ in range(2 * WIDE_BATCH)]
    assert eng.pow_batch(bases, exps) == [
        pow(b, e, P384) for b, e in zip(bases, exps)
    ]
    h = 2 * WIDE_BATCH // 2
    got = eng.dual_pow_batch(bases[:h], exps[:h], bases[h:], exps[h:])
    assert got == [
        pow(a, x, P384) * pow(b, y, P384) % P384
        for a, x, b, y in zip(bases[:h], exps[:h], bases[h:], exps[h:])
    ]


def test_384bit_group_full_protocol_xla(jax_cpu_devices, device_pinned):
    """The whole TPKE + coin round-trip under the 384-bit group on the
    XLA engine — the seam swap the module docstrings promise."""
    _roundtrip(GROUP384, "tpu")


def test_2048bit_modp14_xla_engine_matches_pow(
    jax_cpu_devices, device_pinned
):
    """Round-3 verdict item: the 2048-bit MODP-14 group runs on the
    TPU path (11x192-limb family), property-matched against python
    pow.  Replaces test_xla_engine_rejects_oversized_group."""
    import random

    rng = random.Random(5)
    eng = get_engine("tpu", group=GROUP14)
    assert eng.backend == "tpu"
    assert eng._host_floor(WIDE_BATCH) is None  # really the device path
    bases = [rng.randrange(2, GROUP14.p) for _ in range(WIDE_BATCH)]
    exps = [rng.randrange(1, GROUP14.q) for _ in range(WIDE_BATCH)]
    assert eng.pow_batch(bases, exps) == [
        pow(b, e, GROUP14.p) for b, e in zip(bases, exps)
    ]
    h = WIDE_BATCH // 2
    got = eng.dual_pow_batch(bases[:h], exps[:h], bases[h:], exps[h:])
    assert got == [
        pow(a, x, GROUP14.p) * pow(b, y, GROUP14.p) % GROUP14.p
        for a, x, b, y in zip(bases[:h], exps[:h], bases[h:], exps[h:])
    ]


def test_wide_floors_route_by_measured_crossover(jax_cpu_devices):
    """Engine defaults route per limb family by WIDE_FLOORS (values
    carried over from an earlier attachment of the chip, unmeasured on
    the present one): 384-bit goes to the device at >= 256 exps;
    2048-bit must ALWAYS delegate to the host."""
    eng384 = get_engine("tpu", group=GROUP384)
    assert eng384._host_floor(255) is not None  # below floor -> host
    assert eng384._host_floor(256) is None  # above -> device
    eng2048 = get_engine("tpu", group=GROUP14)
    for b in (8, 256, 1 << 16):
        host = eng2048._host_floor(b)
        assert host is not None and host.backend == "cpu"


def test_xla_engine_still_rejects_beyond_every_family():
    """layout_for_group must return None past the widest family (a
    matching-anyway bug would silently TRUNCATE limbs instead of
    raising)."""
    from cleisthenes_tpu.ops.modmath import layout_for_group

    p_huge = (1 << 3000) + 117  # odd, 3001 bits > 2112-bit family
    g_huge = GroupParams(p=p_huge, q=(p_huge - 1) // 2, g=4)
    assert layout_for_group(g_huge) is None
    with pytest.raises(ValueError, match="limb family"):
        get_engine("tpu", group=g_huge)


def test_groups_are_isolated():
    """Shares dealt in one group must not verify under a key from
    another (the transcript binds the group via element widths and
    reductions)."""
    pub_a, shares_a = tpke.deal(N, F + 1, seed=9, group=GROUP2)
    pub_b, _ = tpke.deal(N, F + 1, seed=9)  # default group
    svc_a = tpke.Tpke(pub_a)
    ct = svc_a.encrypt(b"x" * 32)
    share = svc_a.dec_share(shares_a[0], ct)
    assert tpke.verify_shares(
        pub_b, ct.c1 % pub_b.group.p, [share], svc_a.context(ct)
    ) == [False]


def test_full_protocol_under_second_group():
    """The seam reaches the protocol plane: a 4-node HBBFT network
    whose dealer issued keys in GROUP2 (ciphertext wire width, subgroup
    validation, share issuance/verification and coin all in the
    non-default group) commits identical batches."""
    from tests.test_honeybadger import (
        assert_identical_batches,
        push_txs,
    )
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.honeybadger import HoneyBadger, setup_keys
    from cleisthenes_tpu.transport.base import HmacAuthenticator
    from cleisthenes_tpu.transport.broadcast import ChannelBroadcaster
    from cleisthenes_tpu.transport.channel import ChannelNetwork

    cfg = Config(n=4, batch_size=8)
    ids = [f"node{i}" for i in range(4)]
    keys = setup_keys(cfg, ids, seed=33, group=GROUP2)
    assert keys[ids[0]].tpke_pub.group is GROUP2
    net = ChannelNetwork()
    nodes = {}
    for nid in ids:
        hb = HoneyBadger(
            config=cfg,
            node_id=nid,
            member_ids=ids,
            keys=keys[nid],
            out=ChannelBroadcaster(net, nid, ids),
        )
        nodes[nid] = hb
        net.join(nid, hb, HmacAuthenticator(nid, keys[nid].mac_keys))
    txs = push_txs(nodes, 12, prefix=b"g2")
    for _ in range(6):
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        if all(hb.pending_tx_count() == 0 for hb in nodes.values()):
            break
    depth = assert_identical_batches(nodes)
    committed = {
        tx
        for b in nodes["node0"].committed_batches[:depth]
        for tx in b.tx_list()
    }
    assert committed == set(txs)
