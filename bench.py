"""Benchmarks: north-star crypto plane + real-protocol epochs.

Sections, one JSON line total (the driver contract):

1. **Crypto plane @ north star** (primary metric): wall-clock p50 of
   ONE HBBFT epoch's hot-path crypto at BASELINE north-star scale —
   N=128, f=42, 10k-tx batch — 'tpu' backend vs the CPU reference
   path.  Work per epoch (docs/HONEYBADGER-EN.md:93-96 cost model):
     - RS-encode every validator's proposal into N shards  [N encodes]
     - build the Merkle forest over all N shard sets       [N trees]
     - verify the N^2 ECHO-phase Merkle branches           [N^2 proofs]
     - RS-decode N proposals from K surviving shards       [N decodes]
     - verify N^2 threshold-decryption shares              [N^2 CP]

2. **Real protocol @ N=16 and N=64** (BASELINE primary metric "tx/sec
   & epoch p50 at N=64/128"): full HBBFT epochs over the in-proc
   ChannelNetwork — every message crossing the wire codec and MAC
   layer, crypto routed through the CryptoHub's wave-batched
   dispatches — 'tpu' vs 'cpu' backend.  Warm-up epochs consume their
   own transactions; measured epochs are guaranteed PROTO_EPOCHS.

3. **Order-then-settle overlap** (ISSUE 8): chained real-protocol
   epochs through the two-frontier commit split
   (Config.order_then_settle) vs the coupled arm on the identical
   seeded workload — ``pipeline_overlap_x`` is serial epoch walls /
   elapsed wall, so > 1.0 certifies epoch e+1's RBC/BBA genuinely ran
   under epoch e's trailing decryption.  (Replaces the retired
   crypto_n512_pipelined software-pipeline section, whose ~0.95
   "overlap" measured one dispatch queue against itself.)

4. **Same-box interleaved A/B** (``--ab BASE_REF``): HEAD vs a named
   git ref run alternately in one harness lifetime with paired
   deltas (tools/abench.py) — cross-box BENCH_* comparisons do not
   reproduce (WAVE_EVIDENCE.md), paired same-box runs do.

The run measures in THIS process and only on a TPU: any other JAX
platform exits 2 before a number is taken, and ``platform`` /
``device`` / ``device_count`` record what JAX reported, so every
number names the device it came from.

``vs_baseline`` > 1 means the accelerated path beats the CPU
reference.  Comparator note: the CPU reference uses the native C++ GF
kernels when they build AND the native C++ Montgomery modexp kernel
(native/modpow256.cpp, ~12us per 256-bit exponentiation) — an honest
optimized-native baseline, not python pow().
"""

import json
import math
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tools import benchlock  # noqa: E402


def _append_trend(result: dict) -> None:
    """Fold the finished artifact into the perf-regression trend
    (BENCH_TREND.jsonl; tools/perfgate.py gates CI against it).
    Best-effort: trend bookkeeping must never sink a measurement."""
    try:
        from tools import perfgate

        perfgate.append_bench_trend(result)
    except Exception as exc:  # noqa: BLE001 — recorded, not raised
        print(f"[bench] trend append failed: {exc!r}", file=sys.stderr)


def _load_snapshot() -> dict:
    try:
        return benchlock.load_snapshot()
    except Exception:  # provenance must never sink a measurement
        return {"error": "load_snapshot failed"}


# ---- north-star crypto-plane config (BASELINE.json) ----
N = 128
F = 42
K = N - 2 * F  # 44 data shards
BATCH_TXS = 10_000
TX_BYTES = 64
ITERS = 3
# CP checks per dispatch (2 dual-pows each): the full N^2 = 16,384
# checks of the north-star epoch in ONE dispatch (chunking at 4096
# spends 3 extra dispatches for no compute benefit)
SHARE_VERIFY_CHUNK = 16384

# ---- real-protocol configs ----
PROTO_EPOCHS = 3
PROTO_CONFIGS = {
    "protocol_n16": {"n": 16, "batch": 1024, "epochs": PROTO_EPOCHS},
    "protocol_n64": {"n": 64, "batch": 1024, "epochs": 2},
    # the paper's batch-amortization claim on the REAL path
    # (docs/HONEYBADGER-EN.md:110-113: tx-independent cost dominates
    # at B=1024; by B=16384 the RS/Merkle cost does): measured 10x
    # the tx/sec of the B=1024 row at ~1.5x the epoch latency
    "protocol_n64_b16k": {"n": 64, "batch": 16_384, "epochs": 1},
}
# BASELINE config 4 on the real message-passing path: ~130 s/epoch on
# one core (the whole 128-node cluster serialized in one process), so
# opt-in via BENCH_FULL=1; the default run carries this scale via the
# lockstep section (protocol_spmd_n128) and the crypto-plane metric.
if os.environ.get("BENCH_FULL") == "1":
    PROTO_CONFIGS["protocol_n128"] = {"n": 128, "batch": 2048, "epochs": 1}

# ---- order-then-settle overlap section (ISSUE 8) ----
# The retired crypto_n512_pipelined section measured a SOFTWARE
# pipeline over one dispatch queue (overlap_x ~0.95 — sequential was
# as fast as "pipelined").  pipeline_overlap_x now means what its
# name says: real protocol epochs chained through the two-frontier
# commit split, epoch e+1's RBC/BBA overlapping epoch e's trailing
# decryption, measured as sum(per-epoch propose->settle walls) over
# the elapsed wall (> 1.0 = epochs genuinely overlapped).
OVERLAP_N = 16
OVERLAP_BATCH = 512
OVERLAP_EPOCHS = 4


def payload_bytes(n: int = N, batch: int = BATCH_TXS) -> int:
    # each validator proposes B/N txs (docs/HONEYBADGER-EN.md:51-56)
    return max(batch // n, 1) * TX_BYTES


def epoch_crypto(backend: str, rng: np.random.Generator) -> float:
    """One north-star epoch's batched crypto plane; returns seconds."""
    from cleisthenes_tpu.ops.backend import BatchCrypto
    from cleisthenes_tpu.ops.payload import split_payload
    from cleisthenes_tpu.ops import tpke as tpke_mod

    crypto = BatchCrypto(backend, N, F, K)

    # --- prepare inputs (not timed) ---
    proposals = [
        rng.integers(0, 256, size=payload_bytes(), dtype=np.uint8).tobytes()
        for _ in range(N)
    ]
    data = np.stack([split_payload(p, K) for p in proposals])  # (N, K, L)

    pub, secrets_ = tpke_mod.deal(N, F + 1, seed=123)
    ct = tpke_mod.Tpke(pub).encrypt(b"epoch-key-material")
    ctx = b"bench-ctx"
    shares = [
        tpke_mod.issue_share(secrets_[i % N], ct.c1, ctx) for i in range(N)
    ]

    t0 = time.perf_counter()

    # RS encode all N proposals -> (N, n, L)
    encoded = crypto.erasure.encode_batch(data)

    # Merkle forest: one tree per proposal
    trees = crypto.merkle.build_batch(encoded)

    # ECHO-phase branch verification: N branches per instance = N^2
    roots = np.stack(
        [np.frombuffer(t.root, dtype=np.uint8) for t in trees]
    ).repeat(N, axis=0)
    leaves = encoded.reshape(N * N, -1)
    depth = trees[0].depth
    branches = np.stack(
        [
            np.stack([np.frombuffer(s, dtype=np.uint8) for s in t.branch(j)])
            for t in trees
            for j in range(N)
        ]
    ).reshape(N * N, depth, 32)
    indices = np.tile(np.arange(N), N)
    ok = crypto.merkle.verify_batch(roots, leaves, branches, indices)
    assert bool(ok.all())

    # RS decode: reconstruct each proposal from K surviving shards
    # (the worst-case parity-heavy survivor set)
    survivor_idx = np.arange(N - K, N)
    dec = crypto.erasure.decode_batch(
        np.tile(survivor_idx, (N, 1)),
        encoded[:, survivor_idx, :],
    )
    assert dec.shape == data.shape

    # TPKE share verification: N shares per ciphertext x N ciphertexts,
    # batched through the ModEngine in fixed-size dispatches
    all_shares = shares * N  # N^2 CP proofs
    engine_backend = "cpu" if backend == "cpp" else backend
    for off in range(0, len(all_shares), SHARE_VERIFY_CHUNK):
        res = tpke_mod.verify_shares(
            pub,
            ct.c1,
            all_shares[off : off + SHARE_VERIFY_CHUNK],
            ctx,
            backend=engine_backend,
        )
        assert all(res)

    return time.perf_counter() - t0


def measure_crypto(backend: str) -> float:
    rng = np.random.default_rng(7)
    epoch_crypto(backend, rng)  # warm-up (jit compile)
    times = [epoch_crypto(backend, rng) for _ in range(ITERS)]
    return statistics.median(times)


def cpu_reference_backend() -> str:
    """Honest CPU comparator: the native C++ GF kernels when they
    build, else the numpy reference.  (The modexp comparator is the
    native C++ Montgomery kernel either way — ops/modmath.py routes
    the 'cpu' ModEngine through it.)"""
    try:
        from cleisthenes_tpu.ops.rs_cpp import CppErasureCoder  # noqa: F401

        CppErasureCoder(4, 2)  # forces the compile
        return "cpp"
    except Exception:
        return "cpu"


def modexp_comparator_note() -> str:
    from cleisthenes_tpu.native.build import load_modpow

    if load_modpow() is not None:
        return (
            "CPU modexp baseline: native C++ Montgomery kernel "
            "(native/modpow256.cpp, ~12us/exp)"
        )
    return "CPU modexp baseline: python pow() (native kernel unavailable)"


# ---------------------------------------------------------------------------
# real-protocol benchmark: full HBBFT epochs over the channel transport
# ---------------------------------------------------------------------------


def build_network(
    backend: str, n: int = 16, batch: int = 1024, trace: bool = False
):
    """An in-proc cluster with the shared (cluster-batched) hub — see
    protocol.cluster.SimulatedCluster; manual epoch stepping."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    cfg = Config(
        n=n, batch_size=batch, crypto_backend=backend, seed=99, trace=trace
    )
    cluster = SimulatedCluster(
        config=cfg, key_seed=77, auto_propose=False, shared_hub=True
    )
    return cfg, cluster.net, cluster.nodes, cluster


def two_frontier_keys(metrics) -> dict:
    """The two-frontier per-epoch latencies every protocol section
    reports (ISSUE 8): propose -> ciphertext-ordered commit (what the
    application's ordering sees), propose -> settled plaintext, and
    the trailing decrypt lag's p95.  None on the coupled arm.
    perfgate/abench key on these exact names."""
    return {
        key: round(val * 1000.0, 3) if val is not None else None
        for key, val in (
            ("ordered_epoch_p50_ms", metrics.ordered_latency.p50),
            ("settled_epoch_p50_ms", metrics.epoch_latency.p50),
            ("decrypt_lag_p95_ms", metrics.settle_lag_latency.p95),
        )
    }


def measure_protocol(
    backend: str,
    n: int,
    batch: int,
    epochs: int,
    trace: bool = False,
    trace_out: "str | None" = None,
) -> dict:
    """``epochs`` measured full epochs (plus one untimed warm-up epoch
    with its OWN transactions, so warm-up never eats measured work).
    With ``trace=True`` the cluster runs
    under the flight recorder and the result carries a per-stage
    breakdown of epoch wall time (``stage_shares``) next to
    ``epoch_p50_ms`` — the instrument that makes a BENCH_* number
    explain itself (ISSUE 3)."""
    cfg, net, nodes, cluster = build_network(
        backend, n=n, batch=batch, trace=trace
    )
    rng = np.random.default_rng(13)
    node_ids = sorted(nodes)
    total_txs = batch * (epochs + 1)  # +1: the warm-up epoch's own txs
    for i in range(total_txs):
        tx = rng.integers(0, 256, size=TX_BYTES, dtype=np.uint8).tobytes()
        nodes[node_ids[i % n]].add_transaction(tx)

    # warm-up epoch (jit compile on the tpu backend)
    for hb in nodes.values():
        hb.start_epoch()
    net.run()

    epoch_times = []
    committed = 0
    for _ in range(epochs):
        before = len(nodes[node_ids[0]].committed_batches)
        t0 = time.perf_counter()
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        epoch_times.append(time.perf_counter() - t0)
        after = len(nodes[node_ids[0]].committed_batches)
        committed += sum(
            len(b)
            for b in nodes[node_ids[0]].committed_batches[before:after]
        )
    # agreement sanity: every node committed the identical history
    histories = {
        tuple(tuple(sorted(b.tx_list())) for b in hb.committed_batches)
        for hb in nodes.values()
    }
    assert len(histories) == 1, "protocol benchmark broke agreement"
    p50 = statistics.median(epoch_times) if epoch_times else None
    total_t = sum(epoch_times)
    out = {
        "epoch_p50_ms": round(p50 * 1000.0, 3) if p50 is not None else None,
        # raw per-epoch walls: drift inside one session must be
        # visible in the artifact itself
        "epoch_times_ms": [round(t * 1000.0, 1) for t in epoch_times],
        "tx_per_sec": round(committed / total_t, 1) if total_t > 0 else None,
        "measured_epochs": len(epoch_times),
        # the hub is cluster-shared: this is ALL n validators'
        # device dispatches for the whole run, not a per-node figure
        "hub_dispatches_cluster": int(
            nodes[node_ids[0]].hub.stats()["dispatches"]
        ),
        # wave-columnar counters (ISSUE 7): how wide the hub's flush
        # columns ran and how few dispatches an epoch needed — the
        # numbers the columnar refactor is supposed to move
        "dispatches_per_epoch": round(
            nodes[node_ids[0]].hub.stats()["dispatches"]
            / max(1, epochs + 1),  # +1: warm-up epoch dispatches too
            1,
        ),
    }
    widths = sorted(nodes[node_ids[0]].hub.wave_widths)
    if widths:
        out["wave_width_p50"] = widths[len(widths) // 2]
        out["wave_width_p95"] = widths[
            max(0, int(round(0.95 * (len(widths) - 1))))
        ]
    # delivery-plane columnarization counters (ISSUE 9): payload
    # decodes and MAC-verify calls the whole run actually executed —
    # deterministic for the seeded schedule, cluster-wide (the shared
    # ChannelNetwork serves all n validators), normalized per epoch
    # (+1: the warm-up epoch's traffic counts too)
    dstats = net.delivery_stats()
    run_epochs = epochs + 1
    out["frames_decoded_per_epoch"] = round(
        dstats["frames_decoded"] / run_epochs, 1
    )
    out["mac_verifies_per_epoch"] = round(
        dstats["mac_verifies"] / run_epochs, 1
    )
    probes = dstats["decode_memo_hits"] + dstats["decode_memo_misses"]
    out["decode_memo_hit_rate"] = (
        round(dstats["decode_memo_hits"] / probes, 4) if probes else 0.0
    )
    # wave-routed ingest (ISSUE 10): batch handler invocations
    # crossing the router seam, cluster-wide (all n nodes), per epoch
    # — deterministic for the seeded schedule, the counter the router
    # exists to collapse (one per payload scalar; one per kind per
    # wave routed)
    out["handler_dispatches_per_epoch"] = round(
        sum(
            hb.metrics.handler_dispatches.value for hb in nodes.values()
        )
        / run_epochs,
        1,
    )
    # egress columnarization (ISSUE 13): outbound payload bodies
    # actually encoded, Authenticator sign passes, the encode memo's
    # hit rate, and native coin-share issue dispatches — deterministic
    # for the seeded schedule, cluster-wide, per epoch (the numbers
    # the egress/coin wave batching exists to collapse)
    out["frames_encoded_per_epoch"] = round(
        dstats["frames_encoded"] / run_epochs, 1
    )
    out["mac_signs_per_epoch"] = round(
        dstats["mac_signs"] / run_epochs, 1
    )
    eprobes = dstats["encode_memo_hits"] + dstats["encode_memo_misses"]
    out["encode_memo_hit_rate"] = (
        round(dstats["encode_memo_hits"] / eprobes, 4) if eprobes else 0.0
    )
    out["coin_dispatches_per_epoch"] = round(
        nodes[node_ids[0]].hub.stats()["coin_issue_batches"] / run_epochs,
        1,
    )
    out.update(two_frontier_keys(nodes[node_ids[0]].metrics))
    if trace:
        from cleisthenes_tpu.utils.trace import to_chrome
        from tools import tracetool

        doc = to_chrome(cluster.trace_events())
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(doc, f)
        out["stage_shares"] = tracetool.stage_shares(doc)
        out["trace_stats"] = nodes[node_ids[0]].metrics.snapshot()["trace"]
    return out


def measure_spmd(
    backend: str, n: int, batch: int, epochs: int, group=None
) -> dict:
    """Full-protocol lockstep epochs (protocol.spmd.LockstepCluster):
    every epoch performs the complete deduplicated cryptographic work
    of an N-validator HBBFT epoch — real RS/Merkle/branch-verify, real
    threshold coin per BBA round, optimistic threshold decryption —
    under the benign synchronous schedule (see the module docstring
    for exactly what is and is not exercised)."""
    from cleisthenes_tpu.protocol.spmd import LockstepCluster

    cluster = LockstepCluster(
        n=n,
        batch_size=batch,
        crypto_backend=backend,
        key_seed=77,
        group=group,
    )
    rng = np.random.default_rng(13)
    total = (batch // n) * n * (epochs + 1)
    for _ in range(total):
        tx = rng.integers(0, 256, size=TX_BYTES, dtype=np.uint8).tobytes()
        cluster.submit(tx)
    cluster.run_epoch()  # warm-up (compiles)
    times = []
    committed = 0
    rounds = []
    for _ in range(epochs):
        before = len(cluster.committed_batches)
        s = cluster.run_epoch()
        times.append(s["epoch_s"])
        rounds.append(s["bba_rounds"])
        committed += sum(
            len(b) for b in cluster.committed_batches[before:]
        )
    p50 = statistics.median(times)
    total_t = sum(times)
    return {
        "epoch_p50_ms": round(p50 * 1000.0, 3),
        "epoch_times_ms": [round(t * 1000.0, 1) for t in times],
        "tx_per_sec": round(committed / total_t, 1) if total_t else None,
        "measured_epochs": epochs,
        "bba_rounds": rounds,
    }


# ---------------------------------------------------------------------------
# Wide-group modexp: the XLA limb families past 256 bits
# ---------------------------------------------------------------------------

# RFC 3526 MODP group 14 (2048-bit safe prime)
_MODP14 = int(
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


# RFC 2409 First Oakley Group (768-bit safe prime) — sized for the
# (11, 72) limb family, so all three wide families get a measured
# device-vs-host number (WIDE_FLOORS provenance)
_OAKLEY1 = int(
    "FFFFFFFFFFFFFFFFC90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74"
    "020BBEA63B139B22514A08798E3404DDEF9519B3CD3A431B302B0A6DF25F1437"
    "4FE1356D6D51C245E485B576625E7EC6F44C42E9A63A3620FFFFFFFFFFFFFFFF",
    16,
)


def measure_modexp_wide() -> dict:
    """exps/s of the wide XLA limb families (384/768/2048-bit groups)
    vs the host comparator — python pow here, since the native
    Montgomery kernel is 256-bit-only."""
    from cleisthenes_tpu.ops import modmath as mm

    rng = np.random.default_rng(29)
    out = {}
    for label, p, batch in (
        ("384", mm.P384, 2048),  # the packaged 384-bit group's prime
        ("768", _OAKLEY1, 512),  # (11,72) family
        ("2048", _MODP14, 128),
    ):
        group = mm.GroupParams(p=p, q=(p - 1) // 2, g=4)
        # uncached engine (get_engine's per-group cache would leak the
        # pin below into protocol sections), device-pinned: WIDE_FLOORS
        # would route the 2048-bit batch (measured 0.97x host) back to
        # the host and this section would measure pow against pow
        eng = mm.ModEngine("tpu", group=group)
        eng.host_delegation = False
        bases = [
            int.from_bytes(rng.bytes(group.nbytes), "big") % p
            for _ in range(batch)
        ]
        exps = [
            int.from_bytes(rng.bytes(group.nbytes), "big") % group.q
            for _ in range(batch)
        ]
        got = eng.pow_batch(bases, exps)  # warm-up (compiles)
        t0 = time.perf_counter()
        eng.pow_batch(bases, exps)
        dev_s = time.perf_counter() - t0
        sample = max(batch // 16, 8)
        t0 = time.perf_counter()
        host = [pow(b, e, p) for b, e in zip(bases[:sample], exps[:sample])]
        host_s = (time.perf_counter() - t0) * (batch / sample)
        assert got[:sample] == host, f"{label}-bit device/host mismatch"
        out[f"w{label}"] = {
            "bits": int(label),
            "batch": batch,
            "device_exps_per_sec": round(batch / dev_s, 1),
            "host_pow_exps_per_sec": round(batch / host_s, 1),
            "vs_host": _vs(host_s * 1000.0, dev_s * 1000.0),
        }
    return out


def _vs(cpu_ms, tpu_ms):
    """cpu/tpu ratio, None-safe and NaN-safe (ADVICE round-2)."""
    if (
        isinstance(cpu_ms, (int, float))
        and isinstance(tpu_ms, (int, float))
        and math.isfinite(cpu_ms)
        and math.isfinite(tpu_ms)
        and tpu_ms > 0
    ):
        return round(cpu_ms / tpu_ms, 3)
    return None


def protocol_section(backend_accel: str, backend_cpu: str, n: int,
                     batch: int, epochs: int) -> dict:
    accel = measure_protocol(backend_accel, n, batch, epochs)
    cpu = measure_protocol(backend_cpu, n, batch, epochs)
    return {
        "n": n,
        "batch": batch,
        "tpu": accel,
        "cpu": cpu,
        "vs_cpu": _vs(cpu["epoch_p50_ms"], accel["epoch_p50_ms"]),
    }


# ---------------------------------------------------------------------------
# order-then-settle overlap: the REAL pipelining number (ISSUE 8)
# ---------------------------------------------------------------------------


def measure_order_overlap(
    backend: str,
    n: int = OVERLAP_N,
    batch: int = OVERLAP_BATCH,
    epochs: int = OVERLAP_EPOCHS,
    order_then_settle: bool = True,
    pipeline_depth: int = 1,
) -> dict:
    """Chained protocol epochs through the two-frontier commit split:
    transactions pre-submitted, ``auto_propose`` on, ONE ``net.run``
    drives every epoch back to back, so epoch e+1's RBC/BBA genuinely
    overlaps epoch e's trailing decryption (Config.order_then_settle).

    ``pipeline_overlap_x`` = sum of per-epoch propose->settle walls /
    elapsed wall.  Strictly sequential epochs score <= 1.0; overlap
    pushes it above 1.0.  ``order_then_settle=False`` measures the
    coupled arm of the SAME workload — the honest comparison the
    retired crypto_n512_pipelined section never had."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    # the lead must clear depth + the default lag (read off the
    # dataclass, never a re-stated literal)
    lag = Config.__dataclass_fields__["decrypt_lag_max"].default
    cfg = Config(
        n=n,
        batch_size=batch,
        crypto_backend=backend,
        seed=99,
        order_then_settle=order_then_settle,
        # K-deep pipelined frontiers (ISSUE 15): the section sweeps
        # depth ∈ {1, 2, 4}, so K concurrent epochs share waves and
        # the per-ordered-epoch dispatch counters below move
        pipeline_depth=pipeline_depth,
        reconfig_lead=max(8, pipeline_depth + lag + 1),
    )
    cluster = SimulatedCluster(
        config=cfg, key_seed=77, auto_propose=True, shared_hub=True
    )
    rng = np.random.default_rng(13)
    node_ids = cluster.ids
    # warm-up epoch (jit compile, caches) with its own transactions —
    # add_transaction never opens an epoch, so the kick is explicit
    for i in range(batch):
        cluster.nodes[node_ids[i % n]].add_transaction(
            rng.integers(0, 256, size=TX_BYTES, dtype=np.uint8).tobytes()
        )
    for hb in cluster.nodes.values():
        hb.start_epoch()
    cluster.net.run()
    n0 = cluster.nodes[node_ids[0]]
    assert n0.settled_epoch >= 1, "warm-up epoch did not commit"
    for i in range(batch * epochs):
        cluster.nodes[node_ids[i % n]].add_transaction(
            rng.integers(0, 256, size=TX_BYTES, dtype=np.uint8).tobytes()
        )
    # time.monotonic, NOT perf_counter: the window filter below
    # compares t0 against Metrics' phase stamps, which are
    # time.monotonic values — the two clocks' epochs are not
    # comparable on every platform
    t0 = time.monotonic()
    for hb in cluster.nodes.values():  # kick; auto-propose chains on
        hb.start_epoch()
    cluster.net.run()
    elapsed = time.monotonic() - t0
    assert n0.settled_epoch == n0.epoch, "run ended with unsettled epochs"
    histories = {
        tuple(tuple(sorted(b.tx_list())) for b in hb.committed_batches)
        for hb in cluster.nodes.values()
    }
    assert len(histories) == 1, "overlap benchmark broke agreement"
    m = n0.metrics
    # per-epoch serial walls from the metrics phase traces: the warm-up
    # epoch predates t0, so only spans measured inside the window count
    measured = [
        (e, tp, tc)
        for e, tp, tc in m.epoch_spans()
        if tp >= t0 - 1e-9 and tc is not None
    ]
    spans = [(tp, tc) for _e, tp, tc in measured]
    serial = sum(tc - tp for tp, tc in spans)
    # THE two-frontier certificate: how much of the ordered->settled
    # lag (the trailing decrypt track) ran hidden under some epoch's
    # protocol window [propose, ordered].  The coupled arm has no
    # settle track at all (t_ordered unset) and scores 0 — unlike the
    # serial/elapsed ratio, which the pre-existing proposal pipelining
    # inflates on BOTH arms.
    protocol_iv = []
    settle_iv = []
    for e, tp, tc in measured:
        t_ord = m.trace(e).t_ordered
        protocol_iv.append((tp, t_ord if t_ord is not None else tc))
        if t_ord is not None:
            settle_iv.append((t_ord, tc))
    merged = []
    for p0, p1 in sorted(protocol_iv):
        if merged and p0 <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], p1))
        else:
            merged.append((p0, p1))
    settle_total = sum(s1 - s0 for s0, s1 in settle_iv)
    settle_hidden = sum(
        max(0.0, min(s1, p1) - max(s0, p0))
        for s0, s1 in settle_iv
        for p0, p1 in merged
    )
    # K-deep wave-sharing counters (ISSUE 15): cluster-wide hub/router
    # dispatch totals over the measured run, normalized per ORDERED
    # epoch — K concurrent epochs landing in the same delivery waves
    # is exactly a drop in these (the zero-noise evidence rule)
    ordered_total = max(
        1,
        n0.metrics.epochs_ordered.value or n0.settled_epoch,
    )
    hub_stats = n0.hub.stats()
    handler_total = sum(
        hb.metrics.handler_dispatches.value
        for hb in cluster.nodes.values()
    )
    widths = sorted(n0.hub.wave_widths)
    out = {
        "n": n,
        "batch": batch,
        "mode": (
            "order_then_settle" if order_then_settle else "coupled"
        ),
        "pipeline_depth": pipeline_depth,
        "measured_epochs": len(spans),
        "elapsed_wall_ms": round(elapsed * 1000.0, 3),
        "serial_epoch_walls_ms": round(serial * 1000.0, 3),
        # > 1.0 means epochs genuinely overlapped (an epoch's settle
        # ran under a later epoch's RBC/BBA); sequential epochs bound
        # this at <= ~1.0 by construction
        "pipeline_overlap_x": (
            round(serial / elapsed, 3) if elapsed > 0 else None
        ),
        # fraction of the settle track hidden under protocol windows
        # (0.0 on the coupled arm — it has no settle track)
        "settle_hidden_frac": (
            round(settle_hidden / settle_total, 3)
            if settle_total > 0
            else 0.0
        ),
        "settle_track_ms": round(settle_total * 1000.0, 3),
        "epoch_p50_ms": (
            round(statistics.median([tc - tp for tp, tc in spans])
                  * 1000.0, 3)
            if spans
            else None
        ),
        # per-ordered-epoch dispatch amortization (counter-based,
        # deterministic for the seeded schedule)
        "hub_dispatches_per_ordered_epoch": round(
            hub_stats["dispatches"] / ordered_total, 1
        ),
        "hub_flushes_per_ordered_epoch": round(
            hub_stats["flushes"] / ordered_total, 1
        ),
        "handler_dispatches_per_ordered_epoch": round(
            handler_total / ordered_total, 1
        ),
        "eager_share_waves": int(
            sum(
                hb.metrics.eager_share_waves.value
                for hb in cluster.nodes.values()
            )
        ),
        "wave_width_p50": (
            widths[len(widths) // 2] if widths else None
        ),
        # same index rule as the protocol sections above, so the key
        # means the same thing in every section of one report
        "wave_width_p95": (
            widths[max(0, int(round(0.95 * (len(widths) - 1))))]
            if widths
            else None
        ),
    }
    out.update(two_frontier_keys(m))
    return out


def order_overlap_section(backend: str) -> dict:
    """The same seeded workload across the commit/pipelining arms:
    the two-frontier split at K-deep window depths 1, 2 and 4
    (ISSUE 15 — depth 1 is the lockstep comparison arm) vs the
    coupled commit path — all paired on one box, back to back."""
    depths = {
        depth: measure_order_overlap(
            backend, order_then_settle=True, pipeline_depth=depth
        )
        for depth in (1, 2, 4)
    }
    split = depths[1]
    coupled = measure_order_overlap(backend, order_then_settle=False)
    return {
        "n": OVERLAP_N,
        "batch": OVERLAP_BATCH,
        "epochs": OVERLAP_EPOCHS,
        "order_then_settle": split,
        "depth2": depths[2],
        "depth4": depths[4],
        "coupled": coupled,
        # the headline: settled-throughput ratio of split vs coupled
        # on identical submitted work (elapsed wall, lower is better)
        "split_vs_coupled_wall_x": _vs(
            coupled["elapsed_wall_ms"], split["elapsed_wall_ms"]
        ),
        # K-deep headlines: overlap and wall ratio per depth vs the
        # depth-1 arm of the identical workload, plus the wave-width
        # delta (K epochs sharing waves widens each hub flush)
        "pipeline_overlap_x_by_depth": {
            str(d): depths[d]["pipeline_overlap_x"] for d in depths
        },
        "depth4_vs_depth1_wall_x": _vs(
            split["elapsed_wall_ms"], depths[4]["elapsed_wall_ms"]
        ),
        "wave_width_p50_by_depth": {
            str(d): depths[d]["wave_width_p50"] for d in depths
        },
        "hub_dispatches_per_ordered_epoch_by_depth": {
            str(d): depths[d]["hub_dispatches_per_ordered_epoch"]
            for d in depths
        },
    }


# ---------------------------------------------------------------------------
# WAN emulation scenarios (ISSUE 16): geo-realistic schedules
# ---------------------------------------------------------------------------


def measure_wan(backend: str, profile: str, n: int = 4,
                batch: int = 32, epochs: int = 3) -> dict:
    """One seeded WAN profile end to end: n validators over the
    channel transport with the link-model plane mounted, ``epochs``
    committed epochs back to back.  The headline is virtual time per
    settled epoch (the geo-latency cost the link model charges the
    schedule) next to host wall — plus the model's own evidence
    (retransmits, straggler episodes, frames delayed)."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    cfg = Config(n=n, batch_size=batch, crypto_backend=backend, seed=5)
    cluster = SimulatedCluster(
        config=cfg,
        key_seed=55,
        auto_propose=True,
        shared_hub=True,
        wan_profile=profile,
    )
    rng = np.random.default_rng(21)
    t0 = time.perf_counter()
    for _ in range(epochs):
        for _ in range(batch):
            cluster.submit(
                rng.integers(
                    0, 256, size=TX_BYTES, dtype=np.uint8
                ).tobytes()
            )
        cluster.run_until_drained(max_rounds=80)
    wall_ms = (time.perf_counter() - t0) * 1000.0
    cluster.assert_agreement()
    n0 = cluster.nodes[cluster.ids[0]]
    settled = n0.settled_epoch + 1
    assert settled >= epochs, (
        f"wan profile {profile}: settled {settled} < {epochs}"
    )
    stats = cluster.net.wan.stats()
    health = cluster.health()
    return {
        "profile": profile,
        "settled_epochs": settled,
        "virtual_ms_per_epoch": round(
            int(stats["virtual_time_ms"]) / settled, 1
        ),
        "wall_ms_per_epoch": round(wall_ms / settled, 1),
        "frames_delayed": stats["frames_delayed"],
        "retransmits": stats["retransmits"],
        "straggler_episodes": stats["straggler_episodes"],
        "health": health["status"],
    }


def wan_section(backend: str) -> dict:
    """The named profile matrix under the SAME seeded workload: how
    much schedule time each geography charges, and that every profile
    still commits with agreement (the degradation-hardening evidence
    next to the perf numbers)."""
    from cleisthenes_tpu.transport.wan import wan_profile_names

    return {
        profile: measure_wan(backend, profile)
        for profile in wan_profile_names()
    }


def ingress_section() -> dict:
    """Client-visible latency under open-loop ingress load (ISSUE 18):
    a seeded Pareto-bursty client population driven through the
    production admission path (tools/loadgen.py — in-proc twin of the
    client gRPC surface, fee-priority mempool, channel transport),
    one arm per pipeline depth in {1, 4} over the IDENTICAL arrival
    schedule.  Headlines are submit->ordered and submit->settled
    p50/p99 plus sustained settled tx/s; the harness asserts zero
    lost acks and byte-identical settled content across arms before
    reporting anything.  A wan-composed arm (the PR-16 link model
    under the same load) rides along at depth 4.  CPU-plane only —
    the admission path runs in the scheduler, not on the chip."""
    from tools import loadgen

    schedule = loadgen.build_schedule(
        clients=20_000, txs=6_000, ticks=24, seed=7
    )
    arms = {}
    for depth in (1, 4):
        a = loadgen.run_arm(
            schedule, depth=depth, n=4, batch=256, seed=7
        )
        arms[f"depth{depth}"] = {
            k: a[k]
            for k in (
                "submit_to_ordered_ms", "submit_to_settled_ms",
                "tx_per_s", "settled", "evicted", "epochs",
                "ledger_digest",
            )
        }
    digests = {a["ledger_digest"] for a in arms.values()}
    assert len(digests) == 1, f"ingress arms diverged: {arms}"
    wan = loadgen.run_arm(
        schedule, depth=4, n=4, batch=256, seed=7,
        wan_profile="wan_3region",
    )
    arms["depth4_wan_3region"] = {
        k: wan[k]
        for k in (
            "submit_to_ordered_ms", "submit_to_settled_ms",
            "tx_per_s", "settled", "ledger_digest",
        )
    }
    return {
        "clients": 20_000,
        "txs": 6_000,
        "mode": "open-loop Pareto arrivals via the in-proc ingress "
        "twin (tools/loadgen.py); arms share one seeded schedule",
        "arms": arms,
    }


# ---------------------------------------------------------------------------
# lane shard-out scaling (ISSUE 20): S parallel consensus lanes
# ---------------------------------------------------------------------------


def _lane_balanced_txs(S: int, per_lane: int, seed: int) -> dict:
    """Per-lane tx quotas under the PRODUCTION partitioner: random
    64-byte payloads classified by ``lane_of(seed, digest, S)`` until
    every lane holds exactly ``per_lane``.  A scaling benchmark wants
    fixed-shape load per arm (like a fixed batch shape); the natural
    hash skew across (node, lane) admission cells is measured
    separately by the loadgen lane-skew headline."""
    from cleisthenes_tpu.core.merge import lane_of
    from cleisthenes_tpu.core.mempool import tx_digest

    rng = np.random.default_rng(seed)
    quota: dict = {k: [] for k in range(S)}
    while any(len(v) < per_lane for v in quota.values()):
        tx = rng.integers(0, 256, size=TX_BYTES, dtype=np.uint8).tobytes()
        k = lane_of(seed, tx_digest(tx), S)
        if len(quota[k]) < per_lane:
            quota[k].append(tx)
    return quota


def measure_lane_scaling(S: int, n: int = 16, batch: int = 64,
                         epochs_per_lane: int = 4, seed: int = 41,
                         profile: str = "wan_3region") -> dict:
    """One lane-count arm: n validators, S sibling HBBFT lanes over
    the ONE roster/transport/hub, lane-balanced load, run to drain
    under a seeded WAN profile.  Headlines are tx per VIRTUAL second
    (the link-model clock: S lanes' epochs ride the same geo round
    trips, so settled slots per virtual second scale with S) next to
    honest wall tx/s (the serialized one-process scheduler pays S
    lanes' crypto mass sequentially, so wall throughput must NOT be
    read as the scaling evidence) and hub dispatches per ordered
    lane-epoch (the flatness criterion: the wave coalescer carries
    all S lanes' traffic per flush, so dispatch counts must not grow
    ~linearly in S)."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    cfg = Config(
        n=n, batch_size=batch, crypto_backend="cpu", seed=seed, lanes=S
    )
    cluster = SimulatedCluster(
        config=cfg, seed=seed, shared_hub=True, wan_profile=profile
    )
    quota = _lane_balanced_txs(S, batch * epochs_per_lane, seed)
    ids = cluster.ids
    for txs in quota.values():
        for i, tx in enumerate(txs):
            cluster.nodes[ids[i % n]].add_transaction(tx)
    t0 = time.perf_counter()
    cluster.run_until_drained(max_rounds=600)
    wall_s = time.perf_counter() - t0
    cluster.assert_agreement()
    n0 = cluster.nodes[cluster.ids[0]]
    settled_tx = sum(
        sum(len(v) for v in b.contributions.values())
        for b in n0.merged_batches
    )
    assert settled_tx == S * batch * epochs_per_lane, (
        f"lanes={S}: settled {settled_tx} of "
        f"{S * batch * epochs_per_lane} submitted txs"
    )
    virtual_ms = int(cluster.net.wan.stats()["virtual_time_ms"])
    slots = n0.merged_settled_frontier
    ordered = sum(hb.epoch for hb in n0.lanes)
    hub = n0.hub.stats()["dispatches"]
    return {
        "lanes": S,
        "n": n,
        "batch": batch,
        "settled_tx": settled_tx,
        "merged_slots": slots,
        "virtual_ms": virtual_ms,
        "virtual_ms_per_slot": round(virtual_ms / slots, 1),
        "tx_per_virtual_sec": round(settled_tx / (virtual_ms / 1e3), 1),
        "wall_tx_per_sec": round(settled_tx / wall_s, 1),
        "hub_dispatches_per_ordered_epoch": round(hub / ordered, 2),
    }


def lane_scaling_section() -> dict:
    """Horizontal shard-out (ISSUE 20): S ∈ {1, 2, 4} sibling lanes at
    n=16 under one seeded WAN geography, plus one S=4 arm at n=64.

    The scaling headline is latency-bound throughput — tx per virtual
    second on the link-model clock — because in the serialized
    one-process simulation every lane's crypto runs on the same host
    core: wall tx/s CANNOT scale with S here and is reported next to
    the virtual-time number precisely so nobody mistakes either for
    the other.  The flatness headline (hub dispatches per ordered
    lane-epoch) shows the wave coalescer amortizing all S lanes into
    shared flushes — it FALLS with S rather than staying merely
    flat, because one physical wave now carries S lanes' frames."""
    arms = {f"S{S}": measure_lane_scaling(S) for S in (1, 2, 4)}
    # the width arm: the same 4-lane shard-out over a 64-validator
    # roster (f=21), one epoch per lane — evidence the lane axis
    # composes with roster width, not a cadence measurement
    arms["S4_n64"] = measure_lane_scaling(
        4, n=64, epochs_per_lane=1
    )
    s1, s4 = arms["S1"], arms["S4"]
    return {
        "mode": (
            "lane-balanced 64B txs via the production hash "
            "partitioner; run_until_drained under wan_3region; "
            "virtual-time cadence is the scaling evidence, wall tx/s "
            "the honest serialized-simulation cost"
        ),
        "arms": arms,
        "s4_vs_s1_tx_per_virtual_sec_x": _vs(
            1.0 / s1["tx_per_virtual_sec"], 1.0 / s4["tx_per_virtual_sec"]
        ),
        "s4_vs_s1_wall_tx_per_sec_x": _vs(
            1.0 / s1["wall_tx_per_sec"], 1.0 / s4["wall_tx_per_sec"]
        ),
        "hub_dispatches_per_ordered_epoch_by_S": {
            str(a["lanes"]): a["hub_dispatches_per_ordered_epoch"]
            for a in (arms["S1"], arms["S2"], arms["S4"])
        },
    }


# ---------------------------------------------------------------------------
# driver: one process, measured on the attached chip or not at all
# ---------------------------------------------------------------------------


def main() -> int:
    """Measure every section in THIS process and print one JSON line.

    The accelerated arms are device measurements, so the run refuses
    any platform but 'tpu' (exit 2, naming what JAX found) instead of
    recording host numbers under a device metric's name.  No child
    process is started: a chip belongs to one process.  A section
    that fails fails the run."""
    from cleisthenes_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    device_kind = getattr(dev, "device_kind", "")
    if platform != "tpu":
        print(
            f"bench.py: needs a TPU, but JAX's default platform is "
            f"{platform!r} ({device_kind}); no measurement taken",
            file=sys.stderr,
        )
        return 2

    def progress(section: str) -> None:
        print(f"[bench] {section} @ {time.strftime('%H:%M:%S')}",
              file=sys.stderr, flush=True)

    def dispatch_ms() -> float:
        """One tiny forced dispatch, host clock, ms: recorded at start
        AND end so drift inside a session shows in the artifact."""
        import jax.numpy as jnp

        t0 = time.perf_counter()
        np.asarray(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        return round((time.perf_counter() - t0) * 1000.0, 1)

    # exclusive measurement lock: no background sweep may share the
    # host cores while we measure
    with benchlock.hold("bench.py"):
        provenance = {
            "start_utc": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "dispatch_ms_start": dispatch_ms(),
            "host_load_start": _load_snapshot(),
            "compile_cache_dir": cache_dir,
        }
        out: dict = {"provenance": provenance}
        cpu_ref = cpu_reference_backend()
        progress(f"platform={platform} ({device_kind}); crypto_n128 tpu")
        accel_p50 = measure_crypto("tpu")
        progress("crypto_n128 cpu")
        cpu_p50 = measure_crypto(cpu_ref)
        out.update({
            "metric": "epoch_crypto_p50_n128_f42_b10k",
            "value": round(accel_p50 * 1000.0, 3),
            "unit": "ms",
            "vs_baseline": _vs(cpu_p50 * 1000.0, accel_p50 * 1000.0),
            "platform": platform,
            "device": device_kind,
            "device_count": len(jax.devices()),
            "cpu_reference": cpu_ref,
            "baseline_note": (
                "CPU GF plane uses native C++ kernels when available; "
                + modexp_comparator_note()
            ),
        })
        # full-protocol lockstep epochs at the BASELINE config-4 scale
        # (N=128, f=42, 10k-tx batches) — the SPMD executor
        progress("protocol_spmd_n128 cpu")
        spmd_cpu = measure_spmd(cpu_ref, 128, 10_000, 3)
        progress("protocol_spmd_n128 tpu")
        spmd_tpu = measure_spmd("tpu", 128, 10_000, 3)
        out["protocol_spmd_n128"] = {
            "n": 128, "f": 42, "batch": 10_000,
            "mode": "lockstep (protocol.spmd; benign synchronous "
                    "schedule, full dedup'd crypto, wire/MAC layer not "
                    "exercised)",
            "tpu": spmd_tpu,
            "cpu": spmd_cpu,
            "vs_cpu": _vs(spmd_cpu["epoch_p50_ms"], spmd_tpu["epoch_p50_ms"]),
        }
        # The flagship roster under a production-width group: the SAME
        # full lockstep protocol — TPKE, coin, RS, Merkle — with every
        # exponentiation in the 384-bit safe-prime group (BLS12-381
        # base-field width class, (12,32) XLA limb family) instead of
        # the 256-bit research group.  The CPU comparator is python
        # pow at this width (native kernel is 256-only), measured at 1
        # epoch to bound its cost.
        from cleisthenes_tpu.ops.modmath import GROUP384

        progress("protocol_spmd_n128_g384 tpu")
        g384_tpu = measure_spmd("tpu", 128, 10_000, 2, group=GROUP384)
        progress("protocol_spmd_n128_g384 cpu")
        g384_cpu = measure_spmd(cpu_ref, 128, 10_000, 1, group=GROUP384)
        out["protocol_spmd_n128_g384"] = {
            "n": 128, "f": 42, "batch": 10_000,
            "group_bits": 384,
            "mode": "lockstep, GROUP384 end-to-end (TPKE + coin); "
                    "cpu modexp comparator is python pow",
            "tpu": g384_tpu,
            "cpu": g384_cpu,
            "vs_cpu": _vs(
                g384_cpu["epoch_p50_ms"], g384_tpu["epoch_p50_ms"]
            ),
            # the price of width on the SAME backend (vs the 256-bit
            # flagship section above)
            "g384_over_g256_tpu": _vs(
                g384_tpu["epoch_p50_ms"], spmd_tpu["epoch_p50_ms"]
            ),
        }
        # BASELINE config 5 as a TRUE full-protocol run: N=512
        # validators through RBC + BBA + TPKE in lockstep, on the
        # GF(2^16) codec (the reference's codec dependency caps at 256
        # shards, so its lineage cannot express this roster at all):
        # ~1.9M exponentiations per epoch.
        progress("protocol_spmd_n512 tpu")
        n512_tpu = measure_spmd("tpu", 512, 4096, 2)
        progress("protocol_spmd_n512 cpu")
        n512_cpu = measure_spmd(cpu_ref, 512, 4096, 1)
        out["protocol_spmd_n512"] = {
            "n": 512, "f": 170, "batch": 4096,
            "mode": "lockstep, GF(2^16) erasure codec",
            "tpu": n512_tpu,
            "cpu": n512_cpu,
            "vs_cpu": _vs(
                n512_cpu["epoch_p50_ms"], n512_tpu["epoch_p50_ms"]
            ),
        }
        # order-then-settle overlap (ISSUE 8) on the REAL protocol
        # path; the CPU arm is the headline (the split is a
        # protocol-structure win, not a chip win), the accelerated arm
        # rides beside it.
        progress("order_overlap cpu")
        out["order_overlap"] = {"cpu": order_overlap_section(cpu_ref)}
        progress("order_overlap tpu")
        out["order_overlap"]["tpu"] = order_overlap_section("tpu")
        # WAN emulation scenarios (ISSUE 16): virtual geo-latency
        # charged per settled epoch across the named profile matrix.
        # cpu arm only (the link model runs in the scheduler, not on
        # the chip).
        progress("wan_scenarios")
        out["wan_scenarios"] = wan_section(cpu_ref)
        # ingress load (ISSUE 18): client-visible submit->ordered /
        # submit->settled latency through the production admission
        # path.  Scheduler-plane like wan_scenarios — cpu only.
        progress("ingress_load")
        out["ingress_load"] = ingress_section()
        # lane shard-out (ISSUE 20): S sibling consensus lanes over one
        # roster, virtual-time cadence + dispatch flatness vs S.
        # Scheduler-plane like wan_scenarios — cpu only.
        progress("lane_scaling")
        out["lane_scaling"] = lane_scaling_section()
        progress("modexp_wide")
        out["modexp_wide"] = measure_modexp_wide()
        # live-protocol sections (the slowest) run last; both backends
        # run every one (the host floors route sub-floor batches of
        # the 'tpu' arm to the native kernels)
        for name, pc in PROTO_CONFIGS.items():
            progress(name)
            out[name] = protocol_section(
                "tpu", cpu_ref, pc["n"], pc["batch"], pc["epochs"]
            )
        progress("done")
        provenance["end_utc"] = time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        )
        provenance["dispatch_ms_end"] = dispatch_ms()
        provenance["host_load_end"] = _load_snapshot()
    _append_trend(out)
    print(json.dumps(out))
    return 0


def run_trace() -> None:
    """bench.py --trace [--trace-out PATH]: the protocol_n16 scenario
    under the flight recorder (utils/trace.py) — one JSON line whose
    ``stage_shares`` sits next to ``epoch_p50_ms`` and says where the
    epoch's wall time went (rbc/bba/coin/tpke/hub/transport/...), so
    BENCH_* numbers finally explain themselves.  Runs on the CPU
    reference backend: the breakdown is about epoch anatomy, not the
    chip.  ``--trace-out`` additionally writes the Perfetto-loadable
    artifact (docs/TRACING.md)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --trace")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--trace-out", metavar="PATH", default=None)
    args, _unknown = ap.parse_known_args()
    pc = PROTO_CONFIGS["protocol_n16"]
    try:
        # same exclusive-measurement contract as main(): a traced
        # epoch number sharing the core with another capture is
        # contaminated in BOTH directions
        with benchlock.hold("bench.py --trace"):
            result = measure_protocol(
                cpu_reference_backend(),
                pc["n"],
                pc["batch"],
                pc["epochs"],
                trace=True,
                trace_out=args.trace_out,
            )
    except TimeoutError as exc:
        print(
            json.dumps(
                {
                    "metric": "trace_protocol_n16",
                    "error": f"bench lock unavailable: {exc}",
                }
            )
        )
        return
    doc = {
        "metric": "trace_protocol_n16",
        "n": pc["n"],
        "batch": pc["batch"],
        **result,
    }
    # the traced run carries the richest trend record of all: p50 AND
    # stage shares AND the deterministic dispatch count
    _append_trend({"platform": "cpu", "trace_protocol_n16": {
        "n": pc["n"], "batch": pc["batch"], "cpu": result,
    }})
    print(json.dumps(doc))


def run_ab() -> None:
    """bench.py --ab BASE_REF [...]: same-box interleaved A/B vs a git
    ref with paired deltas (tools/abench.py) — the comparison form
    that survives the cross-box irreproducibility WAVE_EVIDENCE.md
    documents.  Holds the measurement lock like every other mode."""
    argv = list(sys.argv[1:])
    argv.remove("--ab")
    from tools import abench

    try:
        with benchlock.hold("bench.py --ab"):
            sys.exit(abench.main(argv))
    except TimeoutError as exc:
        print(
            json.dumps(
                {
                    "metric": "abench_paired",
                    "error": f"bench lock unavailable: {exc}",
                }
            )
        )
        sys.exit(1)


if __name__ == "__main__":
    if "--ab" in sys.argv:
        run_ab()
    elif "--trace" in sys.argv:
        run_trace()
    else:
        sys.exit(main())
