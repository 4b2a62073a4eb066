"""Multi-device crypto-plane sharding tests (SURVEY.md §2.2, §5.7-5.8).

Run on the 8-virtual-CPU-device mesh conftest.py forces — the same
sharding programs a v5e slice would execute, minus the ICI.  Every
test asserts the sharded path agrees bit-for-bit with the single-
device path.
"""

import random

import numpy as np
import pytest

from cleisthenes_tpu.ops import modmath as mm
from cleisthenes_tpu.ops import placement
from cleisthenes_tpu.ops.merkle import XlaMerkle
from cleisthenes_tpu.ops.modmath import ModEngine
from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder
from cleisthenes_tpu.parallel.mesh import CryptoMesh, make_crypto_mesh
from cleisthenes_tpu.utils import trace
from tests.test_modmath_xla import _as_blocks
from tests.test_trace_spans import FakeAnnotation, session, span_tree  # noqa: F401

MESH_SHAPES = [(2, 2), (2, 4), (3, 2)]


@pytest.fixture(scope="module")
def mesh24(jax_cpu_devices):
    return CryptoMesh((2, 4), devices=jax_cpu_devices)


def _pin_device(mp):
    """The floors hold under a mesh as on one device, and these toy
    batches sit below every one of them: pin them to the XLA kernels,
    as the one-device kernel tests do, so that the sharded programs
    are what runs."""
    mp.setattr(ModEngine, "host_delegation", False)
    mp.setattr(XlaMerkle, "HOST_FLOOR_VERIFY", 0)
    mp.setattr(XlaMerkle, "HOST_FLOOR_BUILD_LEAVES", 0)
    mp.setattr(XlaErasureCoder, "HOST_FLOOR_BYTES", 0)


@pytest.fixture
def device_arm(monkeypatch):
    _pin_device(monkeypatch)
    placement.reset()


def _sharded(tally):
    """The tally says every device item ran sharded, and some did."""
    assert tally and all(
        r["mesh_items"] == r["device_items"] and r["host_items"] == 0
        for r in tally.values()
    ), tally
    return sum(r["mesh_items"] for r in tally.values())


class TestCryptoMesh:
    def test_needs_enough_devices(self, jax_cpu_devices):
        with pytest.raises(ValueError):
            CryptoMesh((4, 4), devices=jax_cpu_devices)

    def test_bad_shape_rejected(self):
        with pytest.raises(ValueError):
            CryptoMesh((0, 2))
        with pytest.raises(ValueError):
            CryptoMesh((2,))

    def test_none_passthrough(self):
        assert make_crypto_mesh(None) is None

    def test_axis_names_and_shape(self, mesh24):
        assert mesh24.mesh.axis_names == ("v", "l")
        assert dict(zip(("v", "l"), mesh24.mesh.devices.shape)) == {
            "v": 2,
            "l": 4,
        }

    def test_pad_rows_and_cols(self, mesh24):
        a = np.arange(3 * 5, dtype=np.uint8).reshape(3, 5)
        padded, b = mesh24.pad_rows(a, 4)
        assert padded.shape == (4, 5) and b == 3
        assert (padded[3] == a[0]).all()
        padded, l = mesh24.pad_cols(a, 4)
        assert padded.shape == (3, 8) and l == 5
        assert (padded[:, 5:] == 0).all()


@pytest.mark.usefixtures("device_arm")
class TestShardedErasure:
    """RS codec sharded P('v', None, 'l') vs single-device."""

    @pytest.mark.parametrize("n,f,batch,length", [(8, 2, 8, 256), (7, 2, 5, 130)])
    def test_encode_batch_agrees(self, mesh24, n, f, batch, length):
        from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder

        k = n - 2 * f
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, size=(batch, k, length), dtype=np.uint8)
        plain = XlaErasureCoder(n, k)
        sharded = XlaErasureCoder(n, k, mesh=mesh24)
        np.testing.assert_array_equal(
            plain.encode_batch(data), sharded.encode_batch(data)
        )

    def test_decode_batch_agrees_shared_pattern(self, mesh24):
        from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder

        n, k, batch, length = 8, 4, 8, 192
        rng = np.random.default_rng(6)
        data = rng.integers(0, 256, size=(batch, k, length), dtype=np.uint8)
        plain = XlaErasureCoder(n, k)
        sharded = XlaErasureCoder(n, k, mesh=mesh24)
        enc = plain.encode_batch(data)
        survivors = np.array([n - k + i for i in range(k)])  # parity-heavy
        idx = np.tile(survivors, (batch, 1))
        got = sharded.decode_batch(idx, enc[:, survivors, :])
        np.testing.assert_array_equal(got, data)

    def test_decode_batch_agrees_mixed_patterns(self, mesh24):
        from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder

        n, k, batch, length = 8, 4, 6, 128
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, size=(batch, k, length), dtype=np.uint8)
        sharded = XlaErasureCoder(n, k, mesh=mesh24)
        enc = XlaErasureCoder(n, k).encode_batch(data)
        idx = np.stack(
            [
                np.sort(rng.choice(n, size=k, replace=False))
                for _ in range(batch)
            ]
        )
        shards = np.stack([enc[i, idx[i], :] for i in range(batch)])
        got = sharded.decode_batch(idx, shards)
        np.testing.assert_array_equal(got, data)


@pytest.mark.usefixtures("device_arm")
class TestShardedMerkle:
    """Merkle forest + branch verify sharded P(('v','l')) flat."""

    def test_build_batch_agrees(self, mesh24):
        from cleisthenes_tpu.ops.merkle import XlaMerkle

        rng = np.random.default_rng(8)
        shards = rng.integers(0, 256, size=(5, 8, 200), dtype=np.uint8)
        plain = XlaMerkle().build_batch(shards)
        sharded = XlaMerkle(mesh=mesh24).build_batch(shards)
        for t0, t1 in zip(plain, sharded):
            assert t0.root == t1.root
            for j in range(8):
                assert t0.branch(j) == t1.branch(j)

    def test_verify_batch_agrees(self, mesh24):
        from cleisthenes_tpu.ops.merkle import XlaMerkle

        rng = np.random.default_rng(9)
        shards = rng.integers(0, 256, size=(4, 8, 96), dtype=np.uint8)
        m = XlaMerkle(mesh=mesh24)
        trees = m.build_batch(shards)
        b = 4 * 8
        roots = np.stack(
            [np.frombuffer(t.root, dtype=np.uint8) for t in trees]
        ).repeat(8, axis=0)
        leaves = shards.reshape(b, -1).copy()
        branches = np.stack(
            [
                np.stack([np.frombuffer(s, np.uint8) for s in t.branch(j)])
                for t in trees
                for j in range(8)
            ]
        )
        indices = np.tile(np.arange(8), 4)
        ok = m.verify_batch(roots, leaves, branches, indices)
        assert ok.all()
        leaves[0, 0] ^= 1  # corrupt one shard byte
        ok = m.verify_batch(roots, leaves, branches, indices)
        assert not ok[0] and ok[1:].all()


@pytest.mark.usefixtures("device_arm")
class TestShardedModexp:
    def test_dual_pow_agrees_with_cpu(self, mesh24):
        from cleisthenes_tpu.ops.modmath import P

        rng = np.random.default_rng(10)
        b = 13  # deliberately not divisible by 8: exercises padding
        u1 = [int(x) % P for x in rng.integers(2, 1 << 62, size=b)]
        u2 = [int(x) % P for x in rng.integers(2, 1 << 62, size=b)]
        e1 = [int(x) for x in rng.integers(1, 1 << 62, size=b)]
        e2 = [int(x) for x in rng.integers(1, 1 << 62, size=b)]
        cpu = ModEngine("cpu").dual_pow_batch(u1, e1, u2, e2)
        tpu = ModEngine("tpu", mesh=mesh24).dual_pow_batch(u1, e1, u2, e2)
        assert cpu == tpu
        assert _sharded(placement.snapshot()) == b

    def test_pow_agrees_with_cpu(self, mesh24):
        from cleisthenes_tpu.ops.modmath import G, P, Q

        bases = [G, 9, P - 2, 12345678901234567890 % P]
        exps = [3, Q - 1, 2, 65537]
        cpu = ModEngine("cpu").pow_batch(bases, exps)
        tpu = ModEngine("tpu", mesh=mesh24).pow_batch(bases, exps)
        assert cpu == tpu
        assert _sharded(placement.snapshot()) == 4


@pytest.mark.usefixtures("device_arm")
class TestShardedProtocolE2E:
    def test_hbbft_epoch_with_mesh(self, jax_cpu_devices):
        """Full HBBFT over the channel transport with the crypto plane
        sharded over the (2, 4) CPU mesh — Config.mesh_shape is a live
        knob end to end (the round-1 'dead knob' finding)."""
        from tests.test_honeybadger import (
            assert_identical_batches,
            make_hb_network,
            push_txs,
        )

        cfg, net, nodes = make_hb_network(
            4, batch_size=8, crypto_backend="tpu", mesh_shape=(2, 4)
        )
        assert nodes["node0"].crypto.mesh is not None
        assert nodes["node0"].crypto.mesh.shape == (2, 4)
        push_txs(nodes, 8)
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        assert_identical_batches(nodes)
        # the batched families ran sharded (a single instance's
        # encode or decode is one device's, mesh or not)
        tally = placement.snapshot()
        for fam in ("rs_gf256.encode_batch", "merkle.verify_branches",
                    "modexp_12x22.dual_pow"):
            assert tally[fam]["mesh_items"] == tally[fam]["device_items"] > 0


@pytest.mark.usefixtures("device_arm")
class TestNonPow2Mesh:
    def test_merkle_bucket_handles_six_devices(self, jax_cpu_devices):
        """Regression: a (3, 2) mesh (6 devices) used to infinite-loop
        the Merkle bucket computation (2^k is never divisible by 6)."""
        from cleisthenes_tpu.ops.merkle import XlaMerkle

        mesh = CryptoMesh((3, 2), devices=jax_cpu_devices)
        m = XlaMerkle(mesh=mesh)
        assert m._bucket(5) % 6 == 0
        rng = np.random.default_rng(11)
        shards = rng.integers(0, 256, size=(5, 4, 64), dtype=np.uint8)
        plain = XlaMerkle().build_batch(shards)
        sharded = m.build_batch(shards)
        for t0, t1 in zip(plain, sharded):
            assert t0.root == t1.root


# ---------------------------------------------------------------------------
# PR 29: the comb, the floors and the seam's spans under a mesh
# ---------------------------------------------------------------------------


def _grouped(seed, sizes):
    rnd = random.Random(seed)
    p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
    return [
        (rnd.randrange(2, p), [rnd.randrange(0, q) for _ in range(sz)])
        for sz in sizes
    ]


@pytest.mark.parametrize("entry", ["ints", "cols"])
@pytest.mark.parametrize(
    "shape,g_row,sizes",
    [
        # the real row width: 600 splits into 512 + 88 (the 128 bucket)
        ((2, 2), None, (600, 3)),
        # a narrow row, so that small groups cross it and leave tails:
        # 37 = 16 + 16 + 5 (buckets 16 and 8), 16 fills a row, 5 pads
        ((2, 4), 16, (37, 16, 5, 70)),
        # six devices divide no power of two: rows pad to lcm(8, 6)
        ((3, 2), 16, (37, 16, 5, 70)),
    ],
    ids=["2x2", "2x4", "3x2"],
)
def test_sharded_comb_matches_the_host_engine(
    jax_cpu_devices, monkeypatch, shape, g_row, sizes, entry
):
    """The comb's program sharded by base row over the mesh, through
    both entry points, against the host engine (native kernel) and
    ``pow()`` on seeded random groups; every item tallied as sharded."""
    monkeypatch.setattr(ModEngine, "HOST_FLOOR", 6)  # comb floor: 64
    if g_row is not None:
        monkeypatch.setattr(ModEngine, "G_ROW", g_row)
    mesh = CryptoMesh(shape, devices=jax_cpu_devices)
    eng = ModEngine("tpu", mesh=mesh)
    groups = _grouped(hash(shape) % 1000 + len(entry), sizes)
    want = ModEngine("cpu").pow_batch_grouped(groups)
    placement.reset()
    if entry == "ints":
        got = eng.pow_batch_grouped(groups)
    else:
        outs = eng.pow_grouped_cols(_as_blocks(groups))
        assert all(o.shape == (1, len(e), 33) for o, (_b, e) in zip(outs, groups))
        got = [mm.bytes33_to_ints(o[0]) for o in outs]
    assert got == want
    p = mm.DEFAULT_GROUP.p
    for (base, exps), res in zip(groups, got):
        assert res[0] == pow(base, exps[0], p)
        assert res[-1] == pow(base, exps[-1], p)
    tally = placement.snapshot()
    assert list(tally) == ["modexp_12x22.comb"]
    assert _sharded(tally) == sum(sizes)
    # one dispatch a size bucket, each with rows a multiple of the mesh
    row = g_row or ModEngine.G_ROW
    buckets = {
        ModEngine._bucket(min(row, sz - off))
        for sz in sizes for off in range(0, sz, row)
    }
    assert tally["modexp_12x22.comb"]["mesh_calls"] == len(buckets)
    assert eng._rows_bucket(5) % mesh.n_devices == 0


def test_sharded_comb_block_of_rows(jax_cpu_devices, monkeypatch):
    """A block of several base rows (one share wave: a row a pair) over
    a (2, 2) mesh: each row's table builds where its exponents are."""
    monkeypatch.setattr(ModEngine, "HOST_FLOOR", 6)
    mesh = CryptoMesh((2, 2), devices=jax_cpu_devices)
    rnd = random.Random(41)
    p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
    bases = [rnd.randrange(2, p) for _ in range(11)]  # pads to 16 rows
    exps = [[rnd.randrange(0, q) for _ in range(8)] for _ in bases]
    block = (bases, np.stack([mm.exps_to_bytes(e) for e in exps]))
    out = ModEngine("tpu", mesh=mesh).pow_grouped_cols([block])[0]
    assert out.shape == (11, 8, 33)
    for r, (base, row) in enumerate(zip(bases, exps)):
        assert mm.bytes33_to_ints(out[r]) == [pow(base, e, p) for e in row]


class TestFloorsUnderAMesh:
    """One floor policy, mesh or not: a batch one device would leave
    on the native host kernel is not spread over the mesh, and the
    tally says which side ran it."""

    def test_modexp(self, mesh24, monkeypatch):
        placement.reset()
        eng = ModEngine("tpu", mesh=mesh24)
        one = ModEngine("tpu")
        rnd = random.Random(5)
        p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
        u = [rnd.randrange(1, p) for _ in range(40)]
        e = [rnd.randrange(0, q) for _ in range(40)]
        groups = _grouped(6, (30, 50))
        # the decisions are the one-device engine's, batch for batch
        for b in (1, 40, ModEngine.HOST_FLOOR - 1, ModEngine.HOST_FLOOR):
            assert (eng._host_floor(b) is None) == (one._host_floor(b) is None)
            assert eng._comb_plan(b) == one._comb_plan(b)
        want = ModEngine("cpu").dual_pow_batch(u, e, u, e)
        assert eng.dual_pow_batch(u, e, u, e) == want
        assert eng.pow_batch(u, e) == ModEngine("cpu").pow_batch(u, e)
        assert eng.pow_batch_grouped(groups) == (
            ModEngine("cpu").pow_batch_grouped(groups)
        )
        tally = placement.snapshot()
        for fam, items in (("dual_pow", 40), ("pow", 40), ("comb", 80)):
            row = tally["modexp_12x22." + fam]
            assert row["host_items"] == items and row["host_calls"] == 1
            assert row["device_items"] == row["mesh_items"] == 0
        # over the floor: the same calls run sharded, and say so
        monkeypatch.setattr(ModEngine, "HOST_FLOOR", 6 * 32)  # comb: 64
        monkeypatch.setattr(ModEngine, "host_delegation", False)
        placement.reset()
        assert eng.dual_pow_batch(u, e, u, e) == want
        assert eng.pow_batch_grouped(groups) == (
            ModEngine("cpu").pow_batch_grouped(groups)
        )
        tally = placement.snapshot()
        assert tally["modexp_12x22.dual_pow"]["mesh_items"] == 40
        assert tally["modexp_12x22.comb"]["mesh_items"] == 80
        assert _sharded(tally) == 120

    def test_erasure_and_merkle(self, mesh24, monkeypatch):
        placement.reset()
        n, k, batch, length = 8, 4, 8, 192
        rng = np.random.default_rng(12)
        data = rng.integers(0, 256, size=(batch, k, length), dtype=np.uint8)
        coder = XlaErasureCoder(n, k, mesh=mesh24)
        merkle = XlaMerkle(mesh=mesh24)
        idx = np.tile(np.arange(k), (batch, 1))

        def calls():
            full = coder.encode_batch(data)
            back = coder.decode_batch(idx, full[:, :k])
            trees = merkle.build_batch(full)
            return full, back, [t.root for t in trees]

        host = calls()  # 6 KiB a batch, 64 leaves: under every floor
        np.testing.assert_array_equal(host[1], data)
        tally = placement.snapshot()
        for fam in ("rs_gf256.encode_batch", "rs_gf256.decode_batch",
                    "merkle.build_forest"):
            assert tally[fam]["host_calls"] == 1, fam
            assert tally[fam]["device_items"] == tally[fam]["mesh_items"] == 0
        monkeypatch.setattr(XlaErasureCoder, "HOST_FLOOR_BYTES", 1024)
        monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_BUILD_LEAVES", 64)
        placement.reset()
        sharded = calls()
        np.testing.assert_array_equal(sharded[0], host[0])
        np.testing.assert_array_equal(sharded[1], host[1])
        assert sharded[2] == host[2]
        tally = placement.snapshot()
        assert tally["rs_gf256.encode_batch"]["mesh_items"] == batch
        assert tally["rs_gf256.decode_batch"]["mesh_items"] == batch
        assert tally["merkle.build_forest"]["mesh_items"] == batch * n
        _sharded(tally)


def _seam_spans(log):
    """[(name, args)] of the mesh seam's spans, and a check that each
    lies directly under an ``ops/device`` span."""
    found = []

    def walk(nodes, parent):
        for name, args, children in nodes:
            if name in ("ops/shard", "ops/gather"):
                assert parent == "ops/device", (name, parent)
                found.append((name, args))
            walk(children, name)

    walk(span_tree(log), None)
    return found


def test_seam_spans_under_a_mesh_and_none_without(
    session, device_arm, mesh24  # noqa: F811
):
    """``ops/shard`` and ``ops/gather``: children of ``ops/device``
    with the device count and the layout, one pair a sharded batch;
    one device has no seam and no such span."""
    rng = np.random.default_rng(13)
    u = [int(x) for x in rng.integers(2, 1 << 62, size=9)]
    data = rng.integers(0, 256, size=(8, 4, 64), dtype=np.uint8)
    idx = np.stack([np.sort(rng.choice(8, size=4, replace=False))
                    for _ in range(8)])

    def calls(mesh):
        ModEngine("tpu", mesh=mesh).dual_pow_batch(u, u, u, u)
        coder = XlaErasureCoder(8, 4, mesh=mesh)
        full = coder.encode_batch(data)
        coder.decode_batch(idx, np.stack([full[i, idx[i]] for i in range(8)]))
        XlaMerkle(mesh=mesh).build_batch(full)

    FakeAnnotation.log = []
    calls(None)
    assert _seam_spans(FakeAnnotation.log) == []
    assert not {"ops/shard", "ops/gather"} & set(trace.totals())
    # one device's tally is what it was before the mesh columns
    # and the bytes are those of the arrays handed in: 9 rows of two
    # (33-byte value, 32-byte exponent) pairs; (8, 4, 64) data and
    # shards; the (8, 8, 64) shard set hashed
    row = dict(device_calls=1, host_calls=0, host_items=0, host_bytes=0,
               mesh_calls=0, mesh_items=0, mesh_bytes=0)
    assert placement.snapshot() == {
        "merkle.build_forest": dict(row, device_items=64, device_bytes=4096),
        "modexp_12x22.dual_pow": dict(
            row, device_items=9, device_bytes=9 * 2 * 65
        ),
        "rs_gf256.decode_batch": dict(row, device_items=8, device_bytes=2048),
        "rs_gf256.encode_batch": dict(row, device_items=8, device_bytes=2048),
    }

    FakeAnnotation.log = []
    trace.reset_totals()
    placement.reset()
    calls(mesh24)
    spans = _seam_spans(FakeAnnotation.log)
    assert all(args["devices"] == 8 for _n, args in spans)
    assert [(n, a["layout"]) for n, a in spans] == [
        ("ops/shard", "flat"), ("ops/gather", "flat"),  # dual_pow
        ("ops/shard", "vl"), ("ops/gather", "vl"),  # encode_batch
        # mixed patterns: the shards, then a matrix an instance
        ("ops/shard", "vl"), ("ops/shard", "v"), ("ops/gather", "vl"),
        ("ops/shard", "flat"), ("ops/gather", "flat"),  # forest
    ]
    totals = trace.totals()
    assert totals["ops/shard"]["calls"] == 5
    assert totals["ops/gather"]["calls"] == 4
    assert _sharded(placement.snapshot()) == 9 + 8 + 8 + 64


@pytest.fixture(scope="module")
def lockstep_arms(jax_cpu_devices):
    """One seeded N=8 lockstep run of two epochs on the cpu backend,
    on one device and on the (2, 2) mesh, floors pinned as the kernel
    tests pin them and the comb's brought down to an N=8 share wave."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.spmd import LockstepCluster

    mp = pytest.MonkeyPatch()
    _pin_device(mp)
    mp.setattr(ModEngine, "HOST_FLOOR", 6)
    arms = {}
    try:
        for arm, backend, mesh_shape in (
            ("cpu", "cpu", None), ("one", "tpu", None), ("mesh", "tpu", (2, 2))
        ):
            placement.reset()
            cluster = LockstepCluster(
                config=Config(n=8, batch_size=64, crypto_backend=backend,
                              mesh_shape=mesh_shape),
                key_seed=31,
            )
            rng = np.random.default_rng(17)
            for _ in range(128):
                cluster.submit(rng.bytes(64))
            rounds = [cluster.run_epoch()["bba_rounds"] for _ in range(2)]
            arms[arm] = {
                "batches": [b.contributions for b in cluster.committed()],
                "rounds": rounds,
                "tally": placement.snapshot(),
            }
    finally:
        mp.undo()
    return arms


def test_lockstep_epoch_on_the_mesh_commits_what_one_device_commits(
    lockstep_arms,
):
    cpu, one, mesh = (lockstep_arms[a] for a in ("cpu", "one", "mesh"))
    assert len(mesh["batches"]) == 2
    assert mesh["batches"] == one["batches"] == cpu["batches"]
    assert mesh["rounds"] == one["rounds"] == cpu["rounds"]


def test_lockstep_epoch_on_the_mesh_shards_the_comb(lockstep_arms):
    """Under a mesh the grouped share waves run the comb sharded (before
    PR 29: flattened to the generic kernel, no comb item at all), and
    every family's decisions, calls and items are the one-device arm's."""
    one, mesh = lockstep_arms["one"]["tally"], lockstep_arms["mesh"]["tally"]
    comb = mesh["modexp_12x22.comb"]
    assert comb["device_items"] == comb["mesh_items"] > 0
    sides = ("device_calls", "device_items", "host_calls", "host_items")
    # delivery apart: one device fuses decode, re-encode and the roots
    # into one program, the mesh still takes the three steps
    delivery = {
        "rs_gf256.decode_recheck", "rs_gf256.decode_batch",
        "rs_gf256.encode_batch", "merkle.build_forest",
    }
    assert {
        f: [r[k] for k in sides] for f, r in mesh.items() if f not in delivery
    } == {
        f: [r[k] for k in sides] for f, r in one.items() if f not in delivery
    }
    assert "rs_gf256.decode_recheck" not in mesh
    assert one["rs_gf256.decode_recheck"]["device_calls"] == 2
    assert mesh["rs_gf256.decode_batch"]["mesh_calls"] == 2
    assert mesh["rs_gf256.encode_batch"]["mesh_calls"] == 2 + 2
    assert mesh["merkle.build_forest"]["mesh_calls"] == 2 + 2
    assert all(
        r["mesh_items"] == r["device_items"] for r in mesh.values()
    ), mesh
    assert all(
        r["mesh_calls"] == r["mesh_items"] == 0 for r in one.values()
    ), one
    assert lockstep_arms["cpu"]["tally"] == {}
