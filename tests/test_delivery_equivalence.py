"""The columnar delivery plane and the wave router (ISSUE 9, 10).

Inbound work runs at wave granularity: frame decode memoizes on the
signing-prefix digest (transport.message.FrameDecodeMemo), MAC
verification batches through one ``Authenticator.verify_wire_many``
call per wave, RBC receipt state lives in the roster-wide EchoBank,
and the WaveRouter makes one batch handler dispatch per (kind, wave).
These tests hold the shared-prefix decoder to the per-frame decoder's
accept/reject behaviour, run the PR-4 semantic coalitions
(equivocating per-receiver roots included) against the EchoBank and
the router, run the fuzz bands, and check that the whole receive path
is PYTHONHASHSEED-independent.  What a seeded run COMMITS is pinned in
tests/test_seeded_pins.py.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from cleisthenes_tpu.config import Config  # noqa: E402
from cleisthenes_tpu.protocol.cluster import SimulatedCluster  # noqa: E402


# Prints one line digesting the ledger bytes AND the columnar delivery
# structure itself: deterministic frame-decode/MAC-verify counters and
# memo tallies.  Two PYTHONHASHSEED values must produce identical
# lines — hash-order iteration anywhere in the wave-prepare / bank
# path would show up as different counters or ledger bytes.
_DELIVERY_DRIVER = r"""
import hashlib
from cleisthenes_tpu.config import Config
from cleisthenes_tpu.core.ledger import encode_batch_body
from cleisthenes_tpu.protocol.cluster import SimulatedCluster

cluster = SimulatedCluster(
    config=Config(n=4, batch_size=8, seed=909),
    seed=909,
    key_seed=4,
)
for i in range(24):
    cluster.submit(b"dlv-hs-%04d" % i)
cluster.run_epochs()
depth = cluster.assert_agreement()
assert depth >= 2, f"want >=2 committed epochs, got {depth}"
h = hashlib.sha256()
for nid in cluster.ids:
    for epoch, batch in enumerate(cluster.nodes[nid].committed_batches):
        h.update(encode_batch_body(epoch, batch))
d = cluster.net.delivery_stats()
dispatches = sum(
    cluster.nodes[nid].metrics.handler_dispatches.value
    for nid in cluster.ids
)
waves = sum(
    cluster.nodes[nid].metrics.waves_routed.value for nid in cluster.ids
)
print(
    "DELIVERY_DIGEST=%s decoded=%d verifies=%d hits=%d misses=%d "
    "dispatches=%d waves=%d"
    % (
        h.hexdigest(),
        d["frames_decoded"],
        d["mac_verifies"],
        d["decode_memo_hits"],
        d["decode_memo_misses"],
        dispatches,
        waves,
    )
)
"""


def _run_delivery_driver(hashseed: str) -> str:
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = hashseed
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _DELIVERY_DRIVER],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, (
        f"PYTHONHASHSEED={hashseed} delivery run failed:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )
    for line in proc.stdout.splitlines():
        if line.startswith("DELIVERY_DIGEST="):
            return line
    raise AssertionError(f"no delivery digest line:\n{proc.stdout}")


def test_delivery_ordering_identical_across_hash_seeds():
    a = _run_delivery_driver("1")
    b = _run_delivery_driver("2")
    assert a == b, (
        "columnar delivery diverged across PYTHONHASHSEED values:\n"
        f"  {a}\n  {b}\n-> hash-order iteration is leaking into the "
        "wave-prepare / EchoBank path (see staticcheck DET002)"
    )


# ---------------------------------------------------------------------------
# codec-level parity: decode_frame_shared vs decode_frame
# ---------------------------------------------------------------------------


def test_decode_frame_shared_parity_and_rejections():
    """The shared-prefix decoder must accept exactly what the scalar
    decoder accepts (same Message, byte-equal signing prefix), share
    the payload object across a broadcast's frames via the memo, and
    reject the same malformed inputs."""
    from cleisthenes_tpu.transport.message import (
        BbaPayload,
        BbaType,
        FrameDecodeMemo,
        Message,
        decode_frame,
        decode_frame_shared,
        encode_message,
    )

    payload = BbaPayload(BbaType.BVAL, "node0", 3, 1, True)
    msg = Message(
        sender_id="node0", timestamp=12.5, payload=payload,
        signature=b"m" * 32,
    )
    wire = encode_message(msg)
    memo = FrameDecodeMemo()
    got, prefix = decode_frame_shared(wire, memo)
    want, want_prefix = decode_frame(wire)
    assert got == want
    assert bytes(prefix) == want_prefix
    assert (memo.hits, memo.misses) == (0, 1)
    # a sibling frame of the same broadcast (same prefix, different
    # MAC) hits the memo and shares the SAME payload object — the id
    # identity the hub's dedup and the column memos downstream rely on
    sibling = encode_message(
        Message(
            sender_id="node0", timestamp=12.5, payload=payload,
            signature=b"x" * 32,
        )
    )
    got2, _ = decode_frame_shared(sibling, memo)
    assert (memo.hits, memo.misses) == (1, 1)
    assert got2.payload is got.payload
    assert got2.signature == b"x" * 32
    # rejection parity: truncations, trailing junk, bad magic
    for mutant in (
        wire[:10],
        wire[:-1],
        wire + b"\x00",
        b"XXXX" + wire[4:],
    ):
        with pytest.raises(ValueError):
            decode_frame(mutant)
        with pytest.raises(ValueError):
            decode_frame_shared(mutant, FrameDecodeMemo())
    # FIFO eviction: at cap the OLDEST entry goes, never the table
    small = FrameDecodeMemo(cap=2)
    frames = []
    for i in range(3):
        p = BbaPayload(BbaType.BVAL, "node0", i, 0, False)
        frames.append(
            encode_message(
                Message(
                    sender_id="node0", timestamp=1.0, payload=p,
                    signature=b"s" * 32,
                )
            )
        )
        decode_frame_shared(frames[-1], small)
    assert len(small.map) == 2
    decode_frame_shared(frames[2], small)  # newest still resident
    assert small.hits == 1


# ---------------------------------------------------------------------------
# PR-4 semantic coalitions against the EchoBank
# ---------------------------------------------------------------------------


def _drive_coalition(behaviors: dict, n: int, seed: int) -> int:
    """Run a Byzantine coalition; returns the agreed honest depth
    (assert_agreement = identical ledger
    prefixes)."""
    bad = sorted(behaviors)
    cluster = SimulatedCluster(
        n=n,
        config=Config(n=n, batch_size=8),
        seed=seed,
        key_seed=21,
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
    return depth


@pytest.mark.faults
def test_equivocator_coalition_columnar_bank():
    """An Equivocator sends CONFLICTING per-receiver RBC roots: the
    EchoBank's per-(root, instance) counting must keep the quorums
    separate — conflating them would fork or stall the honest
    majority."""
    from cleisthenes_tpu.protocol.byzantine import make_behavior

    behaviors = {"node003": make_behavior("equivocator", seed=31)}
    depth = _drive_coalition(behaviors, n=4, seed=13)
    assert depth >= 1
    assert behaviors["node003"].rewrites > 0, "adversary never lied"


@pytest.mark.faults
def test_bad_dealer_coalition_columnar_bank():
    """BadDealer's structurally-valid wrong shards must burn their
    one-vote bank slots without wedging honest quorums."""
    from cleisthenes_tpu.protocol.byzantine import make_behavior

    behaviors = {"node003": make_behavior("bad_dealer", seed=32)}
    depth = _drive_coalition(behaviors, n=4, seed=17)
    assert depth >= 1
    assert behaviors["node003"].rewrites > 0


@pytest.mark.faults
def test_epoch_sprayer_coalition_columnar_bank():
    """EpochSprayer's far-future spam exercises the demux window in
    front of the bank (no bank rows may be minted for epochs outside
    the window)."""
    from cleisthenes_tpu.protocol.byzantine import (
        CompositeBehavior,
        make_behavior,
    )

    behaviors = {
        "node003": CompositeBehavior(
            [
                make_behavior("epoch_sprayer", seed=33),
                make_behavior("split_voter", seed=34),
            ]
        )
    }
    depth = _drive_coalition(behaviors, n=4, seed=19)
    assert depth >= 1


# ---------------------------------------------------------------------------
# PR-4 semantic coalitions against the wave router (ISSUE 10)
# ---------------------------------------------------------------------------


@pytest.mark.faults
def test_equivocator_coalition_wave_router():
    """Equivocating per-receiver roots through the ROUTER's echo/ready
    columns: the per-(root, instance) EchoBank counting must keep the
    quorums separate when whole waves land in one dispatch."""
    from cleisthenes_tpu.protocol.byzantine import make_behavior

    behaviors = {"node003": make_behavior("equivocator", seed=41)}
    depth = _drive_coalition(behaviors, n=4, seed=23)
    assert depth >= 1
    assert behaviors["node003"].rewrites > 0, "adversary never lied"


@pytest.mark.faults
def test_epoch_sprayer_coalition_wave_router():
    """EpochSprayer's far-future spam exercises the router's
    column-granular demux window (no state minted outside it) and the
    per-payload CATCHUP renudge cadence."""
    from cleisthenes_tpu.protocol.byzantine import (
        CompositeBehavior,
        make_behavior,
    )

    behaviors = {
        "node003": CompositeBehavior(
            [
                make_behavior("epoch_sprayer", seed=42),
                make_behavior("split_voter", seed=43),
            ]
        )
    }
    depth = _drive_coalition(behaviors, n=4, seed=29)
    assert depth >= 1


@pytest.mark.faults
def test_selective_mute_coalition_wave_router():
    """SelectiveMute starves chosen links: waves arrive asymmetric
    per receiver, so the router's per-receiver bundles must still
    drive the honest quorums to agreement."""
    from cleisthenes_tpu.protocol.byzantine import make_behavior

    behaviors = {"node003": make_behavior("selective_mute", seed=44)}
    depth = _drive_coalition(behaviors, n=4, seed=31)
    assert depth >= 1


@pytest.mark.faults
def test_fuzz_band_columnar_delivery():
    """20 sampled composite schedules (semantic behaviors x wire
    faults x crash/partition timelines) on the columnar delivery
    plane — a seed band disjoint from ci.sh's 0:20 smoke band, so the
    delivery plane adds coverage instead of re-running it."""
    from tools.fuzz import run_schedule, sample_schedule

    for seed in range(300, 320):
        v = run_schedule(sample_schedule(seed))
        assert v is None, f"seed {seed}: {v}"


@pytest.mark.slow
@pytest.mark.faults
def test_fuzz_deep_sweep_columnar_delivery():
    """The 200-seed slow band on the columnar delivery plane."""
    from tools.fuzz import run_schedule, sample_schedule

    for seed in range(320, 520):
        v = run_schedule(sample_schedule(seed))
        assert v is None, f"seed {seed}: {v}"


@pytest.mark.faults
def test_fuzz_band_wave_router():
    """20 sampled composite schedules against the WAVE ROUTER — a
    seed band disjoint from the ci.sh smoke band and the PR-9 delivery
    band, so the router seam adds coverage instead of re-running it.
    Wire-fault schedules mount a fault_filter, which on the channel
    transport keeps per-frame decode/verify but still routes the
    verified wave — the seam is exercised under tampering too."""
    from tools.fuzz import run_schedule, sample_schedule

    for seed in range(520, 540):
        v = run_schedule(sample_schedule(seed))
        assert v is None, f"seed {seed}: {v}"


@pytest.mark.slow
@pytest.mark.faults
def test_fuzz_deep_sweep_wave_router():
    """The 200-seed slow band on the wave router."""
    from tools.fuzz import run_schedule, sample_schedule

    for seed in range(540, 740):
        v = run_schedule(sample_schedule(seed))
        assert v is None, f"seed {seed}: {v}"
