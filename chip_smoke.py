"""chip_smoke.py — the quickest proof the system still starts on the chip.

    python3 chip_smoke.py [--stages served,lockstep,kernels,four_chip]

One process, no children that touch JAX, no thread that probes the
device.  It refuses to run anywhere but on a TPU (exit 2 naming the
platform JAX found, nothing on stdout), then drives the normal path
with ``crypto_backend="tpu"`` and holds every result to a host
reference:

- **served**: BASELINE.json config 3 (N=64, f=21, batch 10,000) the
  way a client drives it — tools.loadgen schedule -> ingress twins ->
  mempool -> HoneyBadger -> WaveRouter -> CryptoHub -> ops/, real wire
  codec and MACs, default Config arms — then the identical schedule on
  the ``cpu`` backend.  Pass = loadgen's audits AND equal ledger
  digests AND device work > 0 by the placement tally.
- **lockstep**: LockstepCluster N=128, f=42, 10k-tx batches (the north
  star shape): warm-up + 2 epochs on ``tpu``, same seeds on ``cpu``,
  committed batches byte-identical.
- **kernels**: every kernel family the ``tpu`` backend can select, at
  the largest shape a supported roster gives it (N=128 GF(2^8),
  N=512 GF(2^16) and exponentiation waves), against its host
  reference on a sample.
- **four_chip**: with >= 4 devices, the lockstep epoch over
  ``Config.mesh_shape=(2, 2)``; otherwise ``skipped: N device(s)``,
  the only permitted skip.

Any stage that raises, disagrees with its reference or finds no
device work ends the run non-zero: there is no try/except that
records and continues.  Per stage it prints wall seconds, XLA
compilations (count, seconds, persistent-cache hits, and how many
happened after the stage's warm-up — expected 0), the median of 20
tiny host->device->host round trips, and the placement tally
(ops.placement).  The last stdout line is one JSON object naming the
device as JAX reports it.

The XLA compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``.jax_cache/`` in the checkout (utils.compile_cache).
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import json
import pathlib
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from cleisthenes_tpu.ops import placement

STAGES = ("served", "lockstep", "kernels", "four_chip")

# -- full sizes (tests/test_chip_smoke.py passes toy ones) ----------------
# BASELINE.json config 3; 20k txs = two full batches; 4 open-loop ticks
SERVED = dict(n=64, batch=10_000, txs=20_000, ticks=4)
# BASELINE.json config 4 / the north star
LOCKSTEP = dict(n=128, batch=10_000, epochs=2)
KERNELS = dict(
    # generic pow: a standalone Lagrange-combine wave at N=512
    # (512 x 171 terms) pads to this bucket
    pow_rows=131_072,
    # fused verify+combine wave of an N=512 lockstep round
    dual_rows=524_288,
    # share-issue wave of an N=512 lockstep round: the generator
    # group (2 N^2 exps) plus 2N bases of 2N exps each
    comb_roster=512,
    # (12,32): the N=128 GROUP384 epoch's verify wave bucket;
    # (11,72) has no packaged roster — bench.py's modexp_wide size x4
    wide384_rows=32_768,
    wide792_rows=2_048,
    # (n, f, shard length): split_payload pads L to 128 at 10k-tx batches
    gf256=(128, 42, 128),
    # one proposer's VAL encode at an erasure-bound batch (k*L >= 64 KiB)
    gf256_single_len=1_536,
    gf65536=(512, 170, 128),
    merkle=((128, 128), (512, 128)),  # (trees = leaves per tree, L)
)

# jax.monitoring's name for one jit-cache miss going through
# compile_or_get_cached (jax._src.dispatch.BACKEND_COMPILE_EVENT, 0.9.0)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    """A stage's result was wrong; never caught inside this file."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Counts XLA compilations and the seconds they took, from
    jax.monitoring.  With a warm persistent cache a compilation is a
    cache read: same count, far fewer seconds, and ``cache_hits``
    says how many were reads."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


@functools.cache
def _bump():
    import jax

    return jax.jit(lambda a: a + 1)


def dispatch_round_trip_us(reps: int = 20) -> float:
    """Median wall of ``reps`` tiny forced round trips, in us: a 4 KiB
    numpy array to the device, one jitted add, the result back as
    numpy — the fixed cost every ops/ device call pays at least once."""
    import jax.numpy as jnp

    bump = _bump()
    x = np.zeros((8, 128), dtype=np.int32)
    np.asarray(bump(jnp.asarray(x)))  # compile outside the readings
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(bump(jnp.asarray(x)))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls) * 1e6


class Stage:
    """Book-keeping for one stage: wall, compilations, the warm-up
    mark, the placement tally.  ``finish`` prints the report; a stage
    that raises never reaches it."""

    def __init__(self, name: str, meter: CompileMeter) -> None:
        self.name = name
        self._meter = meter
        self._t0 = time.perf_counter()
        self._c0 = (meter.count, meter.seconds, meter.cache_hits)
        self._warm_count: Optional[int] = None
        placement.reset()
        say(f"stage {name}: start")

    def warmed(self) -> None:
        """Warm-up is over: compilations from here on are findings."""
        if self._warm_count is None:
            self._warm_count = self._meter.count

    def finish(self, expect_device: Sequence[str] = ()) -> Dict:
        """Print the stage report.  ``expect_device`` lists families
        the stage could have sent to the device: those at 0 device
        items are NAMED (the served stage only needs the total > 0;
        the kernels stage requires every one)."""
        m = self._meter
        tally = placement.snapshot()
        device_items = sum(r["device_items"] for r in tally.values())
        never = [
            fam
            for fam in sorted(set(expect_device) | set(tally))
            if tally.get(fam, {}).get("device_items", 0) == 0
        ]
        report = {
            "stage": self.name,
            "tally": tally,
            "wall_s": round(time.perf_counter() - self._t0, 2),
            "compiles": m.count - self._c0[0],
            "compile_s": round(m.seconds - self._c0[1], 2),
            "persistent_cache_hits": m.cache_hits - self._c0[2],
            "compiles_after_warmup": (
                None
                if self._warm_count is None
                else m.count - self._warm_count
            ),
            "dispatch_round_trip_us_p50": round(dispatch_round_trip_us(), 1),
            "device_items": device_items,
            "never_on_device": never,
        }
        say(
            "stage {stage}: wall {wall_s} s; {compiles} compilations in "
            "{compile_s} s ({persistent_cache_hits} persistent-cache "
            "hits), {compiles_after_warmup} after warm-up; tiny "
            "round trip p50 {dispatch_round_trip_us_p50} us".format(**report)
        )
        if tally:
            say(f"  {'placement family':<28}{'device calls/items':>22}"
                f"{'host calls/items':>22}{'of device: sharded':>22}")
            for fam, r in tally.items():
                dev = f"{r['device_calls']} / {r['device_items']}"
                host = f"{r['host_calls']} / {r['host_items']}"
                mesh = f"{r['mesh_calls']} / {r['mesh_items']}"
                say(f"  {fam:<28}{dev:>22}{host:>22}{mesh:>22}")
            say(f"  device items total {device_items}; never on the "
                f"device: {', '.join(never) or 'none'}")
        return report


# -- stage: native kernels --------------------------------------------------


def stage_native() -> None:
    """The three native host kernels must load, from libraries built
    out of the committed sources: without them ModEngine silently
    drops to HOST_FLOOR_NO_NATIVE and the cpu reference to python
    pow(), and every comparison below would mean something else."""
    from cleisthenes_tpu.native import build

    for name, load in (
        ("gf256", build.load_gf256),
        ("modpow256", build.load_modpow),
        ("sha256rows", build.load_sha256),
    ):
        lib = load()
        check(lib is not None, f"native kernel {name} did not build/load")
        src = build.source_path(name)
        digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
        so = pathlib.Path(lib._name)
        check(
            so.name == f"_{name}-{digest}.so",
            f"{so} was not built from the committed {src.name}",
        )
        say(f"native {name}: {so.name} (source sha256 {digest})")


# -- stage: served path -----------------------------------------------------

# every family the GF(2^8) rosters' tpu backend can select
_GF256_ROSTER_FAMILIES = (
    "merkle.build_forest",
    "merkle.verify_branches",
    "modexp_12x22.comb",
    "modexp_12x22.dual_pow",
    "modexp_12x22.pow",
    "rs_gf256.decode",
    "rs_gf256.decode_batch",
    "rs_gf256.decode_recheck",
    "rs_gf256.encode",
    "rs_gf256.encode_batch",
)


def stage_served(
    meter: CompileMeter, *, n: int, batch: int, txs: int, ticks: int,
    seed: int,
) -> Dict:
    from tools import loadgen

    stage = Stage("served", meter)
    schedule = loadgen.build_schedule(
        clients=txs, txs=txs, ticks=ticks, seed=seed
    )

    def after_tick(tick: int, total: int) -> None:
        if tick == 1:
            stage.warmed()  # the first tick's epochs are the warm-up
        say(f"  served tpu arm: tick {tick}/{total}")

    arm = dict(depth=2, n=n, batch=batch, seed=seed)
    t0 = time.perf_counter()
    tpu = loadgen.run_arm(
        schedule, crypto_backend="tpu", progress=after_tick, **arm
    )
    tpu_s = time.perf_counter() - t0
    report = stage.finish(expect_device=_GF256_ROSTER_FAMILIES)
    # the plain reference: same schedule, host backend, same process
    t0 = time.perf_counter()
    cpu = loadgen.run_arm(schedule, crypto_backend="cpu", **arm)
    cpu_s = time.perf_counter() - t0
    say(
        f"  served n={n} batch={batch}: tpu arm settled "
        f"{tpu['settled']}/{tpu['txs']} txs in {tpu['epochs']} epochs, "
        f"{tpu_s:.1f} s; cpu arm {cpu['settled']}/{cpu['txs']} in "
        f"{cpu['epochs']} epochs, {cpu_s:.1f} s"
    )
    say(f"  ledger digest tpu {tpu['ledger_digest']}")
    say(f"  ledger digest cpu {cpu['ledger_digest']}")
    # run_arm already raised on lost acks / settled-vs-ordered /
    # cross-node disagreement
    check(
        tpu["ledger_digest"] == cpu["ledger_digest"],
        "served: tpu and cpu arms settled different ledgers",
    )
    check(
        tpu["settled"] == txs and txs >= 2 * batch,
        f"served: settled {tpu['settled']} of {txs} txs "
        f"(need two full batches of {batch})",
    )
    check(
        report["device_items"] > 0,
        "served: the tpu backend sent NOTHING to the device; families: "
        + ", ".join(report["never_on_device"]),
    )
    report.update(tpu_arm_s=round(tpu_s, 2), cpu_arm_s=round(cpu_s, 2))
    return report


# -- stage: lockstep --------------------------------------------------------


def _lockstep_arm(
    backend: str, *, n: int, batch: int, epochs: int, seed: int,
    mesh_shape=None, on_warm: Callable[[], None] = lambda: None,
) -> List[bytes]:
    """Warm-up epoch + ``epochs`` epochs; returns the committed
    batches as their WAL record bodies (byte-comparable)."""
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.core.ledger import encode_batch_body
    from cleisthenes_tpu.protocol.spmd import LockstepCluster

    cluster = LockstepCluster(
        config=Config(
            n=n, batch_size=batch, crypto_backend=backend,
            mesh_shape=mesh_shape,
        ),
        key_seed=77,
    )
    rng = np.random.default_rng(seed)
    per_epoch = (max(batch, n) // n) * n
    for _ in range(per_epoch * (epochs + 1)):
        cluster.submit(rng.bytes(64))
    for e in range(epochs + 1):
        stats = cluster.run_epoch()
        say(
            f"  lockstep {backend}"
            f"{'' if mesh_shape is None else ' mesh ' + str(mesh_shape)}"
            f" epoch {e}: {stats['epoch_s']:.2f} s "
            f"(bba {stats['bba_s']:.2f} s, {stats['bba_rounds']:.0f} "
            f"rounds, {stats['coin_waves']:.0f} coin waves)"
        )
        if e == 0:
            on_warm()
    check(
        sum(len(b) for b in cluster.committed_batches)
        == per_epoch * (epochs + 1),
        f"lockstep {backend}: committed tx count is off",
    )
    return [
        encode_batch_body(e, b)
        for e, b in enumerate(cluster.committed_batches)
    ]


def _bodies_digest(bodies: Sequence[bytes]) -> str:
    h = hashlib.sha256()
    for body in bodies:
        h.update(body)
    return h.hexdigest()


def stage_lockstep(
    meter: CompileMeter, *, n: int, batch: int, epochs: int, seed: int
) -> Dict:
    stage = Stage("lockstep", meter)
    arm = dict(n=n, batch=batch, epochs=epochs, seed=seed)
    tpu = _lockstep_arm("tpu", on_warm=stage.warmed, **arm)
    report = stage.finish(expect_device=_GF256_ROSTER_FAMILIES)
    cpu = _lockstep_arm("cpu", **arm)
    say(f"  committed digest tpu {_bodies_digest(tpu)}")
    say(f"  committed digest cpu {_bodies_digest(cpu)}")
    check(tpu == cpu, "lockstep: tpu and cpu committed batches differ")
    check(
        report["device_items"] > 0,
        "lockstep: the tpu backend sent nothing to the device",
    )
    report["bodies"] = tpu
    return report


# -- stage: kernel families -------------------------------------------------


def _rand_ints(rng, count: int, nbytes: int, mod: int) -> List[int]:
    raw = rng.bytes(count * nbytes)
    return [
        int.from_bytes(raw[i * nbytes : (i + 1) * nbytes], "big") % mod
        for i in range(count)
    ]


def _sample(rng, count: int, k: int = 32) -> List[int]:
    return sorted(
        int(i) for i in rng.choice(count, size=min(k, count), replace=False)
    )


def _kernel_modexp(rng, sizes: Dict) -> Dict[str, Callable[[], None]]:
    from cleisthenes_tpu.ops import modmath as mm

    out: Dict[str, Callable[[], None]] = {}

    def family(label: str, group, rows: int, dual_rows: int) -> None:
        eng = mm.get_engine("tpu", group=group)
        p, q, nb = group.p, group.q, group.nbytes
        bases = _rand_ints(rng, rows, nb, p)
        exps = _rand_ints(rng, rows, nb, q)
        picks = _sample(rng, rows)
        want = [pow(bases[i], exps[i], p) for i in picks]

        def run_pow() -> None:
            got = eng.pow_batch(bases, exps)
            check(
                [got[i] for i in picks] == want,
                f"{label}.pow disagrees with pow()",
            )

        u1 = _rand_ints(rng, dual_rows, nb, p)
        e1 = _rand_ints(rng, dual_rows, nb, q)
        u2 = _rand_ints(rng, dual_rows, nb, p)
        e2 = _rand_ints(rng, dual_rows, nb, q)
        dpicks = _sample(rng, dual_rows)
        dwant = [
            pow(u1[i], e1[i], p) * pow(u2[i], e2[i], p) % p for i in dpicks
        ]

        def run_dual() -> None:
            got = eng.dual_pow_batch(u1, e1, u2, e2)
            check(
                [got[i] for i in dpicks] == dwant,
                f"{label}.dual_pow disagrees with pow()",
            )

        out[f"{label}.pow[{rows}]"] = run_pow
        out[f"{label}.dual_pow[{dual_rows}]"] = run_dual

    family(
        "modexp_12x22", mm.DEFAULT_GROUP, sizes["pow_rows"],
        sizes["dual_rows"],
    )
    family(
        "modexp_12x32", mm.GROUP384, sizes["wide384_rows"],
        sizes["wide384_rows"],
    )
    # the (11,72) family: RFC 2409's 768-bit Oakley group 1
    oakley1 = int(
        "FFFFFFFFFFFFFFFFC90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74"
        "020BBEA63B139B22514A08798E3404DDEF9519B3CD3A431B302B0A6DF25F1437"
        "4FE1356D6D51C245E485B576625E7EC6F44C42E9A63A3620FFFFFFFFFFFFFFFF",
        16,
    )
    family(
        "modexp_11x72",
        mm.GroupParams(p=oakley1, q=(oakley1 - 1) // 2, g=4),
        sizes["wide792_rows"], sizes["wide792_rows"],
    )

    # fixed-base comb at the share-issue wave's shape
    gp = mm.DEFAULT_GROUP
    eng = mm.get_engine("tpu", group=gp)
    nn = sizes["comb_roster"]
    groups = [(gp.g, _rand_ints(rng, 2 * nn * nn, 32, gp.q))]
    for r in _rand_ints(rng, 2 * nn, 32, gp.q):
        groups.append((pow(gp.g, r, gp.p), _rand_ints(rng, 2 * nn, 32, gp.q)))
    gpicks = [
        (gi, ei)
        for gi in _sample(rng, len(groups), 8)
        for ei in _sample(rng, len(groups[gi][1]), 4)
    ]
    gwant = [pow(groups[gi][0], groups[gi][1][ei], gp.p) for gi, ei in gpicks]
    total = sum(len(e) for _b, e in groups)

    def run_comb() -> None:
        got = eng.pow_batch_grouped(groups)
        check(
            [got[gi][ei] for gi, ei in gpicks] == gwant,
            "modexp_12x22.comb disagrees with pow()",
        )

    out[f"modexp_12x22.comb[{len(groups)} bases, {total} exps]"] = run_comb
    return out


def _kernel_gf256(rng, sizes: Dict) -> Dict[str, Callable[[], None]]:
    from cleisthenes_tpu.ops.backend import BatchCrypto

    n, f, length = sizes["gf256"]
    k = n - 2 * f
    dev = BatchCrypto("tpu", n, f, k)
    host = BatchCrypto("cpu", n, f, k)
    data = rng.integers(0, 256, size=(n, k, length), dtype=np.uint8)
    full = host.erasure.encode_batch(data)
    tail = np.arange(n - k, n)  # the parity-heavy survivor set
    shared_idx = np.tile(tail, (n, 1))
    # mixed patterns: instance i lost a different window of shards
    mixed_idx = np.stack(
        [np.sort((np.arange(k) + i) % n) for i in range(n)]
    )
    mixed = np.stack([full[i, mixed_idx[i]] for i in range(n)])
    roots = np.stack(
        [
            np.frombuffer(t.root, dtype=np.uint8)
            for t in host.merkle.build_batch(full)
        ]
    )
    one = rng.integers(
        0, 256, size=(k, sizes["gf256_single_len"]), dtype=np.uint8
    )
    one_full = host.erasure.encode(one)

    def encode_batch() -> None:
        check(
            np.array_equal(dev.erasure.encode_batch(data), full),
            "rs_gf256.encode_batch disagrees with the host codec",
        )

    def decode_shared() -> None:
        got = dev.erasure.decode_batch(shared_idx, full[:, tail])
        check(
            np.array_equal(got, data),
            "rs_gf256.decode_batch (shared pattern) is wrong",
        )

    def decode_mixed() -> None:
        got = dev.erasure.decode_batch(mixed_idx, mixed)
        check(
            np.array_equal(got, data),
            "rs_gf256.decode_batch (mixed patterns) is wrong",
        )

    def decode_recheck() -> None:
        got = dev.erasure.decode_recheck_batch(shared_idx, full[:, tail])
        check(got is not None, "rs_gf256.decode_recheck refused the batch")
        check(
            np.array_equal(got[0], data) and np.array_equal(got[1], roots),
            "rs_gf256.decode_recheck disagrees with decode+encode+roots",
        )

    def single() -> None:
        check(
            np.array_equal(dev.erasure.encode(one), one_full),
            "rs_gf256.encode disagrees with the host codec",
        )
        got = dev.erasure.decode(list(tail), one_full[tail])
        check(np.array_equal(got, one), "rs_gf256.decode is wrong")

    shape = f"[{n}x{k}x{length}]"
    return {
        f"rs_gf256.encode_batch{shape}": encode_batch,
        f"rs_gf256.decode_batch shared{shape}": decode_shared,
        f"rs_gf256.decode_batch mixed{shape}": decode_mixed,
        f"rs_gf256.decode_recheck{shape}": decode_recheck,
        f"rs_gf256.encode+decode[{k}x{sizes['gf256_single_len']}]": single,
    }


def _kernel_gf65536(rng, sizes: Dict) -> Dict[str, Callable[[], None]]:
    from cleisthenes_tpu.ops.rs16 import Cpu16ErasureCoder, Xla16ErasureCoder

    n, f, length = sizes["gf65536"]
    k = n - 2 * f
    dev = Xla16ErasureCoder(n, k)
    host = Cpu16ErasureCoder(n, k)
    data = rng.integers(0, 256, size=(n, k, length), dtype=np.uint8)
    picks = _sample(rng, n, 3)
    want = {i: host.encode(data[i]) for i in picks}
    tail = np.arange(n - k, n)
    state: Dict[str, np.ndarray] = {}

    def encode_batch() -> None:
        state["full"] = dev.encode_batch(data)
        for i in picks:
            check(
                np.array_equal(state["full"][i], want[i]),
                "rs_gf65536.encode_batch disagrees with Cpu16ErasureCoder",
            )

    def decode_batch() -> None:
        got = dev.decode_batch(np.tile(tail, (n, 1)), state["full"][:, tail])
        check(
            np.array_equal(got, data),
            "rs_gf65536.decode_batch did not return the data",
        )

    shape = f"[{n}x{k}x{length}]"
    return {
        f"rs_gf65536.encode_batch{shape}": encode_batch,
        f"rs_gf65536.decode_batch{shape}": decode_batch,
    }


def _kernel_merkle(rng, sizes: Dict) -> Dict[str, Callable[[], None]]:
    from cleisthenes_tpu.ops.merkle import CpuMerkle, XlaMerkle

    dev, host = XlaMerkle(), CpuMerkle()
    out: Dict[str, Callable[[], None]] = {}
    for n, length in sizes["merkle"]:
        shards = rng.integers(0, 256, size=(n, n, length), dtype=np.uint8)
        trees = host.build_batch(shards)
        depth = trees[0].depth
        roots = np.repeat(
            np.stack([np.frombuffer(t.root, np.uint8) for t in trees]),
            n, axis=0,
        )
        leaf = np.arange(n)
        branches = np.zeros((n * n, depth, 32), dtype=np.uint8)
        for i, tree in enumerate(trees):
            for d in range(depth):
                branches[i * n : (i + 1) * n, d] = tree.levels[d][
                    (leaf >> d) ^ 1
                ]
        leaves = np.ascontiguousarray(shards.reshape(n * n, length))
        indices = np.tile(leaf, n)
        # one corrupted proof: the verdict must be per-item, not all-true
        bad = int(rng.integers(0, n * n))
        bad_leaves = leaves.copy()
        bad_leaves[bad, 0] ^= 1

        def build(shards=shards, trees=trees) -> None:
            got = dev.build_batch(shards)
            check(
                all(
                    np.array_equal(a, b)
                    for g, t in zip(got, trees)
                    for a, b in zip(g.levels, t.levels)
                ),
                "merkle.build_forest disagrees with the host hasher",
            )

        def verify(
            roots=roots, leaves=leaves, bad_leaves=bad_leaves,
            branches=branches, indices=indices, bad=bad,
        ) -> None:
            ok = dev.verify_batch(roots, leaves, branches, indices)
            check(bool(ok.all()), "merkle.verify_branches refused a proof")
            ok = dev.verify_batch(roots, bad_leaves, branches, indices)
            check(
                not ok[bad] and int(ok.sum()) == len(ok) - 1,
                "merkle.verify_branches missed the corrupted proof",
            )

        out[f"merkle.build_forest[{n}x{n}x{length}]"] = build
        out[f"merkle.verify_branches[{n * n} proofs]"] = verify
    rows = sizes["merkle"][0][0] ** 2
    msgs = rng.integers(0, 256, size=(rows, 65), dtype=np.uint8)

    def hash_batch() -> None:
        check(
            np.array_equal(dev._hash_batch(msgs), host._hash_batch(msgs)),
            "sha256.hash_batch disagrees with the host hasher",
        )

    out[f"sha256.hash_batch[{rows}x65]"] = hash_batch
    return out


_KERNEL_FAMILIES = _GF256_ROSTER_FAMILIES + (
    "modexp_11x72.dual_pow",
    "modexp_11x72.pow",
    "modexp_12x32.dual_pow",
    "modexp_12x32.pow",
    "rs_gf65536.decode_batch",
    "rs_gf65536.encode_batch",
    "sha256.hash_batch",
)


def stage_kernels(meter: CompileMeter, *, sizes: Dict, seed: int) -> Dict:
    """Each family through its public ops/ entry point, twice: the
    first call compiles, the second is the warm one (0 compilations
    expected).  The (11,192) family never dispatches
    (WIDE_FLOORS[...] is None) and is not run."""
    stage = Stage("kernels", meter)
    rng = np.random.default_rng(seed)
    runs: Dict[str, Callable[[], None]] = {}
    for build in (_kernel_modexp, _kernel_gf256, _kernel_gf65536,
                  _kernel_merkle):
        runs.update(build(rng, sizes))
    for label, run in runs.items():
        c0, t0 = meter.count, time.perf_counter()
        run()
        say(f"  {label}: first call {time.perf_counter() - t0:.3f} s "
            f"({meter.count - c0} compilations), matches host")
    stage.warmed()
    for label, run in runs.items():
        t0 = time.perf_counter()
        run()
        say(f"  {label}: second call {time.perf_counter() - t0:.3f} s")
    report = stage.finish(expect_device=_KERNEL_FAMILIES)
    missing = [f for f in _KERNEL_FAMILIES if f in report["never_on_device"]]
    check(
        not missing,
        "kernels: these families never reached the device: "
        + ", ".join(missing),
    )
    return report


# -- stage: four chips ------------------------------------------------------


def stage_four_chip(
    meter: CompileMeter, *, n: int, batch: int, epochs: int, seed: int,
    reference: Optional[List[bytes]],
) -> Dict:
    """The lockstep epoch over Config.mesh_shape=(2, 2) on the real
    devices; ``reference`` is the single-device tpu arm's committed
    batches (run here when the lockstep stage was not).  The mesh
    arm runs the one-device programs sharded, the comb among them,
    under the one-device floors: every batch it sent to the device
    is tallied as sharded."""
    import jax

    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.parallel.mesh import CryptoMesh

    arm = dict(n=n, batch=batch, epochs=epochs, seed=seed)
    if reference is None:
        reference = _lockstep_arm("tpu", **arm)
    stage = Stage("four_chip", meter)
    devices = jax.devices()[:4]
    got = _lockstep_arm(
        "tpu", mesh_shape=(2, 2), on_warm=stage.warmed, **arm
    )
    report = stage.finish()
    tally = report["tally"]
    comb = tally.get("modexp_12x22.comb", {})
    check(
        comb.get("mesh_items", 0) > 0,
        f"four_chip: no comb item ran sharded over the mesh: {comb}",
    )
    check(
        all(r["mesh_items"] == r["device_items"] for r in tally.values()),
        "four_chip: a device batch of the mesh arm ran on one device",
    )
    say(f"  committed digest mesh(2,2) {_bodies_digest(got)}")
    say(f"  committed digest 1 device  {_bodies_digest(reference)}")
    check(
        got == reference,
        "four_chip: the (2, 2) mesh committed different batches than "
        "one device",
    )
    # every device holds shards of both placement layouts the epoch uses
    mesh = CryptoMesh((2, 2))
    k = Config(n=n).data_shards
    for what, arr in (
        ("RS layout P('v', None, 'l')",
         mesh.put_vl(np.zeros((n, k, 128), np.uint8))),
        ("flat layout P(('v','l'))",
         mesh.put_flat(np.zeros((n * n, 33), np.uint8))[0]),
    ):
        holders = {s.device for s in arr.addressable_shards}
        check(
            holders == set(devices),
            f"four_chip: {what} landed on {len(holders)} device(s)",
        )
    peaks = {}
    for d in devices:
        stats = d.memory_stats() or {}
        peaks[str(d)] = int(stats.get("peak_bytes_in_use", 0))
    say(f"  peak_bytes_in_use per device: {peaks}")
    check(
        all(v > 0 for v in peaks.values()),
        f"four_chip: a device never held memory: {peaks}",
    )
    return report


# -- driver -----------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--stages", default=",".join(STAGES),
        help="comma-separated subset of: " + ", ".join(STAGES),
    )
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    stages = [s for s in args.stages.split(",") if s]
    unknown = sorted(set(stages) - set(STAGES))
    if unknown:
        ap.error(f"unknown stage(s): {', '.join(unknown)}")

    from cleisthenes_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX's default platform is "
            f"{device['platform']!r} ({device['kind']} x{device['count']}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 2
    say(
        f"platform {device['platform']}, device_kind {device['kind']}, "
        f"{device['count']} device(s); jax {jax.__version__}; compile "
        f"cache {cache_dir}"
    )
    t_all = time.perf_counter()
    stage_native()
    meter = CompileMeter()
    say(f"tiny round trip p50 at start: {dispatch_round_trip_us():.1f} us")
    check(
        meter.count > 0,
        f"the compile meter saw no {_COMPILE_EVENT} event: it would "
        "report 0 compilations whatever happened",
    )
    lockstep_bodies = None
    if "served" in stages:
        stage_served(meter, seed=args.seed, **SERVED)
    if "lockstep" in stages:
        lockstep_bodies = stage_lockstep(
            meter, seed=args.seed, **LOCKSTEP
        )["bodies"]
    if "kernels" in stages:
        stage_kernels(meter, sizes=KERNELS, seed=args.seed)
    if "four_chip" in stages:
        if device["count"] >= 4:
            stage_four_chip(
                meter, seed=args.seed, reference=lockstep_bodies, **LOCKSTEP
            )
        else:
            say(f"stage four_chip: skipped: {device['count']} device(s)")
    say(
        f"all stages passed in {time.perf_counter() - t_all:.1f} s; "
        f"{meter.count} compilations, {meter.seconds:.1f} s, "
        f"{meter.cache_hits} persistent-cache hits"
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
