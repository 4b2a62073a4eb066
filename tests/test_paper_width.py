"""The HoneyBadgerBFT paper's evaluation width (250-byte transactions,
43 KB shards at N=16): the served path and the kernels at that width,
and the byte counters and benchmark readers that came with it.

(a) a four-validator roster whose proposals cross the RS byte floor by
    their width settles the same ledger on the device path and on the
    host path, and the placement tally's bytes are the shapes';
(b) the GF(2^8) coder and the Merkle kernels at the cell's real shard
    length against the plain reference, byte for byte;
(c) ``delivery_stats()`` ``bytes_decoded`` is the bytes of the payload
    bodies the codec parsed;
(d) the four readers and ``benchmarks/work_erasure.py`` on hand-made
    runs.
"""

from __future__ import annotations

import functools
import hashlib
import pathlib
import signal
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import reference_erasure as ref  # noqa: E402
from benchmarks import spec, work_erasure  # noqa: E402
from cleisthenes_tpu.config import Config  # noqa: E402
from cleisthenes_tpu.core.ledger import encode_batch_body  # noqa: E402
from cleisthenes_tpu.ops import placement  # noqa: E402
from cleisthenes_tpu.ops.backend import BatchCrypto  # noqa: E402
from cleisthenes_tpu.ops.merkle import XlaMerkle  # noqa: E402
from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder  # noqa: E402
from cleisthenes_tpu.protocol.cluster import SimulatedCluster  # noqa: E402

N, F, K = 16, 5, 6
LENGTH = 43_392  # the shard of a full proposal: 1,024 x 250 bytes, k=6
TX_BYTES = 250


def within(seconds: float):
    """The test's own time limit: SIGALRM raises in the test's thread."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            def late(_signum, _frame):
                raise TimeoutError(f"{fn.__name__} took over {seconds} s")

            old = signal.signal(signal.SIGALRM, late)
            signal.setitimer(signal.ITIMER_REAL, seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, old)

        return run

    return wrap


# -- (a) the served path at 250 bytes ----------------------------------------


def _ledger_digest(cluster) -> str:
    h = hashlib.sha256()
    for nid in cluster.ids:
        for epoch, batch in enumerate(cluster.nodes[nid].committed_batches):
            h.update(encode_batch_body(epoch, batch))
    return h.hexdigest()


def _served_run(backend: str, seed: int = 34):
    """Two full epochs of 1,280 transactions of 250 bytes at N=4, f=1,
    k=2: a proposal is 320 transactions, 81 KB, an (2, 40,7xx) matrix."""
    cluster = SimulatedCluster(
        config=Config(n=4, batch_size=1280, seed=seed,
                      crypto_backend=backend),
        seed=seed,
        key_seed=seed + 1,
    )
    rng = np.random.default_rng(seed)
    for i in range(2 * 1280):
        cluster.submit(i.to_bytes(8, "big") + rng.bytes(TX_BYTES - 8))
    cluster.run_epochs()
    depth = cluster.assert_agreement()
    digest = _ledger_digest(cluster)
    cluster.stop()
    return digest, depth


@within(240)
def test_the_served_path_settles_one_ledger_on_device_and_host_paths(
    monkeypatch,
):
    encodes = []
    real_encode = XlaErasureCoder.encode

    def encode(self, data):
        encodes.append(np.asarray(data).shape)
        return real_encode(self, data)

    monkeypatch.setattr(XlaErasureCoder, "encode", encode)
    placement.reset()
    tpu_digest, tpu_depth = _served_run("tpu")
    tally = placement.snapshot()
    cpu_digest, cpu_depth = _served_run("cpu")
    assert (tpu_digest, tpu_depth) == (cpu_digest, cpu_depth)
    assert tpu_depth >= 2

    # every proposal crossed XlaErasureCoder.HOST_FLOOR_BYTES by its
    # width (the floor is the class's own, not patched)
    assert XlaErasureCoder.HOST_FLOOR_BYTES == 1 << 16
    full = [s for s in encodes if s[0] * s[1] >= 1 << 16]
    assert len(full) >= 8 and all(s[0] == 2 for s in encodes)
    assert all(s[1] % 128 == 0 and s[1] >= 320 * 254 // 2 for s in full)
    row = tally["rs_gf256.encode"]
    assert row["device_items"] == row["device_calls"] == len(full)
    assert row["device_bytes"] == sum(k * length for k, length in full)
    assert row["host_bytes"] == sum(
        k * length for k, length in encodes if (k, length) not in full
    )
    # the decode waves too: k shards of the encodes' lengths a matrix
    fused = tally["rs_gf256.decode_recheck"]
    assert fused["device_items"] > 0
    lengths = sorted({length for _k, length in full})
    assert fused["device_bytes"] % (2 * 128) == 0
    assert (
        fused["device_items"] * 2 * lengths[0]
        <= fused["device_bytes"]
        <= fused["device_items"] * 2 * lengths[-1]
    )
    # the Merkle floors count items: a few proofs of 40 KB leaves each
    # stay on the host, and the byte columns say how much that is
    merkle = tally["merkle.verify_branches"]
    assert merkle["device_items"] == 0 and merkle["host_items"] > 0
    assert merkle["host_bytes"] >= merkle["host_items"] * lengths[0]


# -- (b) the kernels at the cell's real shard length ---------------------------


@pytest.fixture(scope="module")
def shard_set():
    rng = np.random.default_rng(250)
    mats = rng.integers(0, 256, (8, K, LENGTH), dtype=np.uint8)
    fulls = np.stack([ref.encode(N, K, m) for m in mats])
    return mats, fulls, [ref.merkle_levels(f) for f in fulls]


@within(120)
def test_encode_at_the_real_length_is_the_references(shard_set):
    mats, fulls, _roots = shard_set
    placement.reset()
    got = XlaErasureCoder(N, K).encode(mats[0])
    assert np.array_equal(got, fulls[0])
    row = placement.snapshot()["rs_gf256.encode"]
    assert (row["device_calls"], row["device_bytes"]) == (1, K * LENGTH)


def _patterns(kind: str):
    if kind == "worst":  # every data shard lost
        return [tuple(range(N - K, N))] * 8
    if kind == "first":  # what a validator holds when a wave came whole
        return [tuple(range(K))] * 8
    rng = np.random.default_rng(7)
    return [
        tuple(sorted(rng.choice(N, size=K, replace=False).tolist()))
        for _ in range(8)
    ]


@pytest.mark.parametrize(
    "kind, dispatches", [("worst", 1), ("first", 1), ("mixed", 3)]
)
@within(240)
def test_decode_recheck_at_the_real_length_is_the_references(
    shard_set, kind, dispatches
):
    mats, fulls, trees = shard_set
    roots = [levels[-1][0] for levels in trees]
    idxs = _patterns(kind)
    shards = np.stack([f[list(ix)] for f, ix in zip(fulls, idxs)])
    placement.reset()
    data, got_roots, n = BatchCrypto("tpu", N, F, K).decode_recheck_batch(
        np.asarray(idxs), shards
    )
    assert n == dispatches  # one fused program, or the three-step path
    assert np.array_equal(data, mats)
    assert [r.tobytes() for r in got_roots] == roots
    d0, r0 = ref.decode_recheck(N, K, idxs[0], shards[0])
    assert np.array_equal(data[0], d0) and got_roots[0].tobytes() == r0
    tally = placement.snapshot()
    if dispatches == 1:
        row = tally["rs_gf256.decode_recheck"]
        assert (row["device_items"], row["device_bytes"]) == (
            8, 8 * K * LENGTH
        )
    else:
        assert tally["rs_gf256.decode_batch"]["device_bytes"] == (
            8 * K * LENGTH
        )
        assert tally["rs_gf256.encode_batch"]["device_bytes"] == (
            8 * K * LENGTH
        )
        # 128 leaves: under the item floor, 5.5 MB hashed on the host
        assert tally["merkle.build_forest"]["host_bytes"] == (
            8 * N * LENGTH
        )


@pytest.mark.parametrize("proofs", [16, 64])
@within(240)
def test_verify_batch_at_the_real_length_is_the_references(shard_set, proofs):
    _mats, fulls, trees = shard_set
    roots = [levels[-1][0] for levels in trees]
    merkle = XlaMerkle()
    merkle.HOST_FLOOR_VERIFY = 0  # this object's: the floor crossed by hand
    rows = [(s, j) for s in range(proofs // N) for j in range(N)]
    leaves = np.stack([fulls[s][j] for s, j in rows])
    leaves[5, 1000] ^= 0x80
    branches = np.stack([
        np.frombuffer(
            b"".join(ref.merkle_branch(trees[s], j)), dtype=np.uint8
        ).reshape(-1, 32)
        for s, j in rows
    ])
    root_arr = np.stack(
        [np.frombuffer(roots[s], dtype=np.uint8) for s, _j in rows]
    )
    placement.reset()
    got = merkle.verify_batch(
        root_arr, leaves, branches, np.asarray([j for _s, j in rows])
    )
    want = [
        ref.verify_branch(roots[s], bytes(leaf), [bytes(b) for b in br], j)
        for (s, j), leaf, br in zip(rows, leaves, branches)
    ]
    assert [bool(x) for x in got] == want
    assert want.count(False) == 1 and want[5] is False
    row = placement.snapshot()["merkle.verify_branches"]
    assert row["device_items"] == proofs
    assert row["device_bytes"] == proofs * (LENGTH + 4 * 32)


# -- (c) the wire's byte counters ----------------------------------------------


@within(120)
def test_bytes_decoded_and_encoded_are_the_payload_bodies(monkeypatch):
    from cleisthenes_tpu.transport import message
    from cleisthenes_tpu.transport.message import decode_frame

    # every payload body the egress memo really built: the top-level
    # calls of the encoder (a lane or a bundle encodes its inner
    # payloads by calling it again)
    built = []
    depth = [0]
    real_encode = message._encode_payload

    def encode(p):
        depth[0] += 1
        try:
            kind, body = real_encode(p)
        finally:
            depth[0] -= 1
        if depth[0] == 0:
            built.append(len(body))
        return kind, body

    monkeypatch.setattr(message, "_encode_payload", encode)
    cluster = SimulatedCluster(
        config=Config(n=4, batch_size=8, seed=11), seed=11, key_seed=12
    )
    frames = []
    cluster.net.frame_tap = lambda _s, _r, wire: frames.append(wire)
    for i in range(24):
        cluster.submit(b"tx250-%04d" % i + bytes(240))
    cluster.run_epochs()
    cluster.assert_agreement()
    stats = cluster.net.delivery_stats()
    cluster.stop()
    monkeypatch.undo()

    # a frame's signing prefix is its envelope and its payload body;
    # the shared-prefix memo parses each distinct prefix once
    bodies = {}
    for wire in frames:
        msg, prefix = decode_frame(wire)
        envelope = 6 + 4 + len(msg.sender_id.encode()) + 8 + 4
        bodies[bytes(prefix)] = len(prefix) - envelope
    assert len(bodies) < 4096  # under the memo's cap: no body parsed twice
    assert stats["frames_decoded"] == len(bodies)
    assert stats["bytes_decoded"] == sum(bodies.values()) > 24 * 250
    assert stats["decode_memo_hits"] == len(frames) - len(bodies)
    assert stats["frames_encoded"] == len(built)
    assert stats["bytes_encoded"] == sum(built) > 24 * 250


# -- (d) the readers and the work functions --------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _row(**fields):
    row = dict.fromkeys(
        ("device_calls", "device_items", "device_bytes", "host_calls",
         "host_items", "host_bytes", "mesh_calls", "mesh_items",
         "mesh_bytes"), 0)
    row.update(fields)
    return row


def _without_bytes(rows):
    """The tally as a program from before the byte columns gives it."""
    return {fam: {k: v for k, v in row.items() if not k.endswith("_bytes")}
            for fam, row in rows.items()}


def _run(placement_after, programs=None, delivery=(None, None), epochs=2):
    """A hand-made ``run`` as benchmarks/run.py hands it to a reader."""
    def counters(rows, i):
        out = {"placement": rows, "epochs": i * epochs, "compiles": 0}
        if delivery[i] is not None:
            out["delivery"] = delivery[i]
        return out

    before, after = counters({}, 0), counters(placement_after, 1)
    return {
        "config": {"config": {"n": N}},
        "device_kind": "TPU v5 lite",
        "counters": {"before": before, "after": after,
                     "trace": {"before": before, "after": after}},
        "trace": None if programs is None else {
            "programs": programs, "window_s": 3.0
        },
    }


def test_the_work_functions_count_from_the_widths_alone():
    in_bytes = 16 * K * LENGTH
    delta = {
        "rs_gf256.encode": _row(device_calls=16, device_items=16,
                                device_bytes=in_bytes),
        "rs_gf256.decode_batch": _row(device_calls=1, device_items=16,
                                      device_bytes=in_bytes),
        "rs_gf256.decode_recheck": _row(device_calls=2, device_items=16,
                                        device_bytes=in_bytes),
    }
    rs = work_erasure.rs256_work(delta, N, K)
    # an encode applies n - k = 10 rows, a decode k = 6: 128 * rows an
    # input byte; an encode writes n/k bytes an input byte, a decode 1
    assert rs["bf16_flops"] == 128 * 10 * in_bytes + 128 * 6 * in_bytes
    assert rs["bytes"] == in_bytes * (6 + 16) // 6 + in_bytes * 2
    fused = work_erasure.decode_recheck_work(delta, N, K)
    assert fused["bf16_flops"] == 128 * 16 * in_bytes
    assert fused["bytes"] == in_bytes * (6 + 6 + 16) // 6 + 16 * 32
    # host batches are no device work; a tally from before the byte
    # columns reads None, not 0
    assert work_erasure.rs256_work(
        {"rs_gf256.encode": _row(host_calls=3, host_bytes=9)}, N, K
    )["in_bytes"] == 0
    old = _without_bytes({"rs_gf256.decode_recheck": _row(
        device_calls=1, device_items=8
    )})
    assert work_erasure.decode_recheck_work(old, N, K) is None


def test_the_yardstick_is_the_larger_of_the_two_times():
    # compute-bound: 1e12 operations are 5.08 ms at the bf16 peak, 1 MB
    # are 1.2 us at the bandwidth
    work = {"bf16_flops": 10**12, "bytes": 10**6}
    assert work_erasure.roofline_pct(work, 0.1, PEAKS) == pytest.approx(
        100 * (10**12 / 197e12) / 0.1
    )
    # memory-bound: 8.19 GB are 10 ms, the same operations 5.08 ms
    work = {"bf16_flops": 10**12, "bytes": 8_190_000_000}
    assert work_erasure.roofline_pct(work, 0.1, PEAKS) == pytest.approx(10.0)
    # a count that read the smaller time would hide a device time that
    # is too short for the bytes: the larger one reads over 100
    assert work_erasure.roofline_pct(work, 0.006, PEAKS) > 100 > (
        100 * (10**12 / 197e12) / 0.006
    )


def test_the_roofline_readers_on_hand_made_runs():
    fused_read = spec.load_reader("decode_recheck_roofline")
    rs_read = spec.load_reader("rs256_roofline")
    in_bytes = 16 * K * LENGTH
    rows = {
        "rs_gf256.decode_recheck": _row(device_calls=1, device_items=16,
                                        device_bytes=in_bytes),
        "rs_gf256.encode": _row(device_calls=16, device_items=16,
                                device_bytes=in_bytes),
    }
    programs = {"jit__decode_recheck_kernel": 0.040,
                "jit__encode_kernel": 0.002, "jit__pow_fused": 1.0}
    run = _run(rows, programs)
    least = 128 * 16 * in_bytes / 197e12  # compute-bound at (16, 6)
    assert least > in_bytes * (2 + 16 / 6) / 819e9
    assert fused_read(run) == pytest.approx(100 * least / 0.040)
    assert 0 < fused_read(run) < 1
    # the encodes too: 10 parity rows, 1,280 operations an input byte
    # (6.5 ps) against 1 + 16/6 bytes moved (4.5 ps)
    assert rs_read(run) == pytest.approx(
        100 * (128 * 10 * in_bytes / 197e12) / 0.002
    )
    # no trace, no such program, or a tally without byte columns: None
    assert fused_read(_run(rows)) is None
    assert fused_read(_run(rows, {"jit__pow_fused": 1.0})) is None
    assert rs_read(_run({}, programs)) is None
    old = _without_bytes(rows)
    assert fused_read(_run(old, programs)) is None
    assert rs_read(_run(old, programs)) is None


def test_the_counter_readers_on_hand_made_runs():
    byte_pct = spec.load_reader("erasure_device_byte_pct")
    wire = spec.load_reader("wire_mb_per_epoch")
    rows = {
        "rs_gf256.encode": _row(device_calls=2, device_items=2,
                                device_bytes=600),
        "merkle.verify_branches": _row(host_calls=1, host_items=256,
                                       host_bytes=1400),
        "sha256.hash_batch": _row(host_calls=1, host_items=3),
        # not an erasure family: left out of the share
        "modexp_12x22.dual_pow": _row(device_calls=1, device_items=9,
                                      device_bytes=10**6),
    }
    assert byte_pct(_run(rows)) == pytest.approx(30.0)
    assert byte_pct(_run({})) is None
    old = _without_bytes(rows)
    assert byte_pct(_run(old)) is None
    # the item twin still reads such a tally
    assert spec.load_reader("rs_device_item_pct")(_run(old)) == 100.0

    new = ({"frames_decoded": 0, "bytes_decoded": 0},
           {"frames_decoded": 960, "bytes_decoded": 44_000_000})
    assert wire(_run({}, delivery=new)) == pytest.approx(22.0)
    older = ({"frames_decoded": 0}, {"frames_decoded": 960})
    assert wire(_run({}, delivery=older)) is None
    assert wire(_run({})) is None  # a lockstep run has no delivery plane
    assert wire(_run({}, delivery=new, epochs=0)) is None


def test_the_time_limit_raises_in_the_test():
    import time

    @within(0.05)
    def slow():
        time.sleep(1.0)

    with pytest.raises(TimeoutError):
        slow()
    assert signal.getitimer(signal.ITIMER_REAL)[0] == 0
