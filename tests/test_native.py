"""Native C++ GF(2^8) kernel tests: property-tested against the numpy
reference backend, plus a full HBBFT epoch on crypto_backend='cpp'."""

import numpy as np
import pytest

from cleisthenes_tpu.native.build import native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain"
)


def test_native_selftest_passes():
    from cleisthenes_tpu.native.build import load_gf256

    assert load_gf256().gf256_selftest() == 0


@pytest.mark.parametrize("n,k", [(4, 2), (7, 3), (16, 6), (64, 22)])
def test_cpp_encode_matches_numpy(n, k):
    from cleisthenes_tpu.ops.rs_cpp import CppErasureCoder
    from cleisthenes_tpu.ops.rs_cpu import CpuErasureCoder

    rng = np.random.default_rng(n * 100 + k)
    data = rng.integers(0, 256, size=(k, 384), dtype=np.uint8)
    assert np.array_equal(
        CppErasureCoder(n, k).encode(data), CpuErasureCoder(n, k).encode(data)
    )


@pytest.mark.parametrize("seed", range(4))
def test_cpp_decode_roundtrip_any_k_survivors(seed):
    from cleisthenes_tpu.ops.rs_cpp import CppErasureCoder

    rng = np.random.default_rng(seed)
    n, k = 10, 4
    coder = CppErasureCoder(n, k)
    data = rng.integers(0, 256, size=(k, 200), dtype=np.uint8)
    full = coder.encode(data)
    survivors = sorted(rng.choice(n, size=k, replace=False).tolist())
    out = coder.decode(survivors, full[survivors])
    assert np.array_equal(out, data)


def test_cpp_encode_batch_matches_single():
    from cleisthenes_tpu.ops.rs_cpp import CppErasureCoder

    rng = np.random.default_rng(3)
    n, k, b = 8, 4, 5
    coder = CppErasureCoder(n, k)
    data = rng.integers(0, 256, size=(b, k, 128), dtype=np.uint8)
    batched = coder.encode_batch(data)
    for i in range(b):
        assert np.array_equal(batched[i], coder.encode(data[i]))


def test_backend_registry_exposes_cpp():
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.ops.backend import get_backend

    cfg = Config(n=4, crypto_backend="cpp")
    crypto = get_backend(cfg)
    assert crypto.engine_backend == "cpu"
    data = np.arange(2 * 128, dtype=np.uint8).reshape(2, 128)
    full = crypto.erasure.encode(data)
    assert np.array_equal(
        crypto.erasure.decode([2, 3], full[2:4]), data
    )


def test_hbbft_epoch_on_cpp_backend():
    from tests.test_honeybadger import (
        assert_identical_batches,
        make_hb_network,
        push_txs,
    )
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.honeybadger import setup_keys
    from cleisthenes_tpu.transport.base import HmacAuthenticator
    from cleisthenes_tpu.transport.broadcast import ChannelBroadcaster
    from cleisthenes_tpu.transport.channel import ChannelNetwork
    from cleisthenes_tpu.protocol.honeybadger import HoneyBadger

    cfg = Config(n=4, batch_size=8, crypto_backend="cpp")
    ids = [f"node{i}" for i in range(4)]
    keys = setup_keys(cfg, ids, seed=11)
    net = ChannelNetwork()
    nodes = {}
    for node_id in ids:
        hb = HoneyBadger(
            config=cfg,
            node_id=node_id,
            member_ids=ids,
            keys=keys[node_id],
            out=ChannelBroadcaster(net, node_id, ids),
        )
        nodes[node_id] = hb
        net.join(node_id, hb, HmacAuthenticator(node_id, keys[node_id].mac_keys))
    push_txs(nodes, 8)
    for hb in nodes.values():
        hb.start_epoch()
    net.run()
    assert_identical_batches(nodes)



class TestSha256Rows:
    def test_matches_hashlib_fixed_and_var(self):
        import hashlib

        import numpy as np

        from cleisthenes_tpu.ops.hashrows import sha256_rows

        rng = np.random.default_rng(3)
        rows = rng.integers(0, 256, size=(97, 131), dtype=np.uint8)
        got = sha256_rows(rows)
        for i in (0, 50, 96):
            assert got[i].tobytes() == hashlib.sha256(rows[i].tobytes()).digest()
        lens = rng.integers(0, 132, size=97)
        got = sha256_rows(rows, lens)
        for i in (0, 13, 96):
            assert (
                got[i].tobytes()
                == hashlib.sha256(rows[i, : int(lens[i])].tobytes()).digest()
            )

    def test_rejects_out_of_range_lens(self):
        import numpy as np
        import pytest

        from cleisthenes_tpu.ops.hashrows import sha256_rows

        rows = np.zeros((2, 8), dtype=np.uint8)
        with pytest.raises(ValueError):
            sha256_rows(rows, np.array([1, 9]))
        with pytest.raises(ValueError):
            sha256_rows(rows, np.array([-1, 4]))

    def test_fallback_path_matches_native(self, monkeypatch):
        """With the native library unavailable the hashlib fallback
        must produce identical digests (it is the degraded path for
        toolchain-less deployments)."""
        import hashlib

        import numpy as np

        import pytest

        from cleisthenes_tpu.ops import hashrows
        from cleisthenes_tpu.native.build import load_sha256

        if load_sha256() is None:
            # without the toolchain "native" would BE the fallback and
            # the comparison below would check it against itself
            pytest.skip("native sha256 unavailable; nothing to compare")
        rng = np.random.default_rng(9)
        rows = rng.integers(0, 256, size=(13, 57), dtype=np.uint8)
        lens = rng.integers(0, 58, size=13)
        native = hashrows.sha256_rows(rows, lens)
        monkeypatch.setattr(hashrows, "load_sha256", lambda: None)
        degraded = hashrows.sha256_rows(rows, lens)
        assert (native == degraded).all()
        # independent hashlib checks for BOTH fallback branches
        for i in (0, 7):
            assert (
                degraded[i].tobytes()
                == hashlib.sha256(rows[i, : int(lens[i])].tobytes()).digest()
            )
        full = hashrows.sha256_rows(rows)
        assert full[3].tobytes() == hashlib.sha256(rows[3].tobytes()).digest()


# Row lengths around SHA-256's padding edges (55/56: one block or two;
# 119/120: two or three), a CP transcript and a 43 KB Merkle leaf.
_SHA_LENGTHS = (0, 1, 55, 56, 63, 64, 65, 119, 120, 128, 230, 43393)


def _hashlib_rows(rows, lens=None):
    import hashlib

    if lens is None:
        lens = [rows.shape[1]] * len(rows)
    return np.stack([
        np.frombuffer(
            hashlib.sha256(rows[i, : int(n)].tobytes()).digest(),
            dtype=np.uint8,
        )
        for i, n in enumerate(lens)
    ])


@pytest.fixture
def sha_lib():
    """The native row hasher; whatever path a test forces, the best
    path is resolved again afterwards."""
    from cleisthenes_tpu.native.build import load_sha256
    from cleisthenes_tpu.ops import hashrows

    lib = load_sha256()
    # the module runs only where the toolchain builds the kernels: a row
    # hasher that did not load failed its selftest
    assert lib is not None, "sha256rows did not build or pass its selftest"
    best = lib.sha256_path()
    hashrows.reset_hash_tally()
    yield lib
    assert lib.sha256_resolve(len(hashrows.PATHS) - 1) == best
    hashrows.reset_hash_tally()


class TestSha256RowsPaths:
    @pytest.mark.parametrize("length", _SHA_LENGTHS)
    def test_fixed_rows_match_hashlib(self, sha_lib, length):
        from cleisthenes_tpu.ops.hashrows import sha256_rows

        rows = np.random.default_rng(length).integers(
            0, 256, size=(5, length), dtype=np.uint8
        )
        assert (sha256_rows(rows) == _hashlib_rows(rows)).all()

    @pytest.mark.parametrize("length", _SHA_LENGTHS)
    def test_varying_lens_match_hashlib(self, sha_lib, length):
        """Rows of every length up to ``length`` in one call: each
        padding edge below it, the stride itself and the empty row."""
        from cleisthenes_tpu.ops.hashrows import sha256_rows

        lens = np.array(
            [length] + [n for n in _SHA_LENGTHS if n < length] + [0],
            dtype=np.int32,
        )
        rows = np.random.default_rng(length + 1).integers(
            0, 256, size=(len(lens), length), dtype=np.uint8
        )
        assert (sha256_rows(rows, lens) == _hashlib_rows(rows, lens)).all()

    @pytest.mark.parametrize(
        "floors,width,less",
        [
            (1, 38, 1),  # one row short of the floor: the calling thread
            (1, 38, 0),  # at the floor: two threads
            (9, 38, 0),  # far above it: six
            (1, 230, 0),  # four-block transcript rows
        ],
        ids=["below", "at", "above", "four_blocks"],
    )
    def test_floor_counts_blocks(self, sha_lib, floors, width, less):
        """The split is decided by 64-byte blocks, not rows: ``floors``
        times the thread floor's blocks, less ``less`` rows."""
        import os

        from cleisthenes_tpu.ops import hashrows

        per_row = (width + 72) // 64
        floor = sha_lib.sha256_thread_floor_blocks()
        m = floors * floor // per_row - less
        rows = np.random.default_rng(m).integers(
            0, 256, size=(m, width), dtype=np.uint8
        )
        assert (hashrows.sha256_rows(rows) == _hashlib_rows(rows)).all()
        blocks = m * per_row
        want = 1  # threads: the square root of the floor's quarters, capped
        if blocks >= floor:
            want = min(int((4 * blocks / floor) ** 0.5), os.cpu_count(), 16)
        tally = hashrows.hash_tally()
        assert tally["blocks"] == blocks
        assert tally["threaded_calls"] == int(want > 1)
        out = np.empty((m, 32), dtype=np.uint8)
        assert want == sha_lib.sha256_rows_fixed(
            rows.ctypes.data, m, width, width, out.ctypes.data, 0
        )

    @pytest.mark.parametrize("threads", [1, 2, 3, 7, 16, 64])
    def test_rows_straddle_the_chunks(self, sha_lib, threads):
        """37 rows of mixed lengths over any number of threads, more
        threads than rows among them: chunk edges fall between rows of
        one, two and three blocks, and no row is hashed twice or not
        at all."""
        rng = np.random.default_rng(threads)
        rows = rng.integers(0, 256, size=(37, 150), dtype=np.uint8)
        lens = rng.integers(0, 151, size=37).astype(np.int32)
        out = np.zeros((37, 32), dtype=np.uint8)
        ran = sha_lib.sha256_rows(
            rows.ctypes.data, 37, 150, lens.ctypes.data, out.ctypes.data,
            threads,
        )
        chunk = -(-37 // threads)  # rows a thread: the last may get fewer
        assert ran == -(-37 // chunk)
        assert (out == _hashlib_rows(rows, lens)).all()
        fixed = np.zeros((37, 32), dtype=np.uint8)
        sha_lib.sha256_rows_fixed(
            rows.ctypes.data, 37, 150, 150, fixed.ctypes.data, threads
        )
        assert (fixed == _hashlib_rows(rows)).all()

    @pytest.mark.parametrize("path", [2, 1, 0], ids=lambda p: f"cap{p}")
    def test_each_path_gives_the_same_digests(self, sha_lib, path):
        """Streaming, one-shot and builtin in turn: the resolver is told
        to find nothing above ``path``, as on a libcrypto without the
        streaming calls or on a host without libcrypto."""
        from cleisthenes_tpu.ops import hashrows

        if sha_lib.sha256_resolve(path) != path:
            pytest.skip(f"libcrypto offers no {hashrows.PATHS[path]} here")
        assert sha_lib.sha256_selftest() == 0
        rng = np.random.default_rng(path)
        rows = rng.integers(0, 256, size=(300, 230), dtype=np.uint8)
        lens = rng.integers(0, 231, size=300)
        assert (hashrows.sha256_rows(rows, lens) == _hashlib_rows(rows, lens)).all()
        leaves = rng.integers(0, 256, size=(8, 43393), dtype=np.uint8)
        assert (hashrows.sha256_rows(leaves) == _hashlib_rows(leaves)).all()
        assert hashrows.hash_tally()["path"] == hashrows.PATHS[path]

    def test_tally_counts_calls_rows_blocks(self, sha_lib, monkeypatch):
        from cleisthenes_tpu.ops import hashrows

        rows = np.zeros((10, 100), dtype=np.uint8)
        hashrows.sha256_rows(rows)  # 10 rows of two blocks
        hashrows.sha256_rows(rows, np.array([0, 55, 56, 100] * 2 + [1, 1]))
        hashrows.sha256_rows(rows[:0])  # nothing to hash: not a call
        assert hashrows.hash_tally() == {
            "calls": 2,
            "rows": 20,
            "blocks": 20 + (1 + 1 + 2 + 2) * 2 + 2,
            "threaded_calls": 0,
            "path": hashrows.PATHS[sha_lib.sha256_path()],
        }
        big = np.zeros((sha_lib.sha256_thread_floor_blocks(), 38), np.uint8)
        hashrows.sha256_rows(big)
        assert hashrows.hash_tally()["threaded_calls"] == 1
        monkeypatch.setattr(hashrows, "load_sha256", lambda: None)
        hashrows.sha256_rows(rows)
        assert hashrows.hash_tally()["path"] == "hashlib"
        hashrows.reset_hash_tally()
        assert hashrows.hash_tally() == {
            "calls": 0, "rows": 0, "blocks": 0, "threaded_calls": 0,
            "path": "",
        }


def _reference_keystream(key, length):
    import hashlib

    blocks = [
        hashlib.sha256(key + ctr.to_bytes(4, "big") + b"ks").digest()
        for ctr in range((length + 31) // 32)
    ]
    return b"".join(blocks)[:length]


class TestKeystreamBytes:
    @pytest.mark.parametrize("length", [1, 32, 480, 511, 512, 513, 8191, 260000])
    def test_keystream_matches_hashlib(self, sha_lib, length):
        """Below 16 blocks the hashlib loop, from 16 on one kernel call
        (a 260 KB proposal's 8,125 rows run threaded)."""
        import hashlib

        from cleisthenes_tpu.ops import tpke

        key = hashlib.sha256(b"kem%d" % length).digest()
        assert tpke._keystream(key, length) == _reference_keystream(key, length)

    def test_encrypt_open_round_trip_matches_hashlib(self, sha_lib):
        """A 260 KB proposal's ciphertext and tag, byte for byte what a
        pure-hashlib hashed ElGamal gives, and open() returns it."""
        import hashlib
        import hmac
        import random

        from cleisthenes_tpu.ops import hashrows, tpke

        pub, _ = tpke.deal(4, 2, seed=41)
        gp = pub.group
        msg = random.Random(41).randbytes(260000)

        class Rng:
            def token_bytes(self, n):
                return random.Random(7).randbytes(n)

        ct = tpke.Tpke(pub).encrypt(msg, rng=Rng())
        r = int.from_bytes(Rng().token_bytes(gp.nbytes + 8), "big") % gp.q
        kem = pow(pub.master, r, gp.p)
        key = hashlib.sha256(b"kem" + kem.to_bytes(gp.nbytes, "big")).digest()
        ks = _reference_keystream(key, len(msg))
        want_c2 = bytes(a ^ b for a, b in zip(msg, ks))
        assert ct.c1 == pow(gp.g, r, gp.p)
        assert ct.c2 == want_c2
        assert ct.tag == hmac.new(
            key, ct.c1.to_bytes(gp.nbytes, "big") + want_c2, hashlib.sha256
        ).digest()
        hashrows.reset_hash_tally()
        assert tpke.Tpke(pub).open(ct, kem) == msg
        assert hashrows.hash_tally()["rows"] == (len(msg) + 31) // 32
