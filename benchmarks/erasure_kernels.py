"""The erasure-code kernels alone, at the shapes `n16-b16k-tx250` meets,
each held byte for byte to the plain reference
(benchmarks/reference_erasure.py), with its time and the device's peak
memory.

    python3 benchmarks/erasure_kernels.py [--seed 1]

One process; exits 2 unless JAX finds a TPU (the gate of
benchmarks/run.py).  Through the program's own entry points, as the
served path calls them: ``XlaErasureCoder.encode`` at (k, L),
``BatchCrypto.decode_recheck_batch`` at (8, k, L) with the worst
erasure pattern (every data shard lost) and with mixed patterns, and at
(256, k, L), and ``XlaMerkle.verify_batch`` over 256 proofs of L-byte
leaves with its item floor set to 0 on that one object (in the cell it
stays on the host).  A time is the host's clock around a call that
ends with the result on the host (transfer in, program, fetch), the
median of ``REPEATS`` calls after the one that compiles; ``host_s`` is
the same call on the ``cpu`` backend (the native host kernels a floor
would choose), for a judgement of the floors.  The last line of stdout
is one JSON object; exit code 1 if any comparison differs.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

N, F, K = 16, 5, 6
LENGTH = 43_392  # a full proposal's shard: 1,024 transactions of 250 bytes
REPEATS = 5


def _timed(call: Callable, repeats: int):
    """(result of the first call, its seconds, median seconds of the
    rest): the first call compiles."""
    t0 = time.perf_counter()
    out = call()
    first = time.perf_counter() - t0
    rest = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        call()
        rest.append(time.perf_counter() - t0)
    return out, first, statistics.median(rest)


def check_kernels(length: int, seed: int, batches=(8, 256),
                  repeats: int = REPEATS) -> Dict:
    """Runs every case; returns {"cases": [...], "equal": bool}."""
    import numpy as np

    from benchmarks import reference_erasure as ref
    from benchmarks.run import memory_peak_bytes
    from cleisthenes_tpu.ops.backend import BatchCrypto
    from cleisthenes_tpu.ops.merkle import XlaMerkle

    rng = np.random.default_rng(seed)
    crypto = BatchCrypto("tpu", N, F, K)
    host = BatchCrypto("cpu", N, F, K)
    cases: List[Dict] = []

    def case(name: str, shape, equal: bool, first: float, median: float,
             host_call: Callable):
        cases.append({
            "case": name, "shape": list(shape), "equal": bool(equal),
            "first_call_s": first, "median_s": median,
            "host_s": _timed(host_call, repeats)[2],
            "memory_peak_bytes": memory_peak_bytes(1),
        })
        print(f"[kernels] {cases[-1]}", flush=True)

    data = rng.integers(0, 256, (K, length), dtype=np.uint8)
    full, first, median = _timed(lambda: crypto.erasure.encode(data), repeats)
    want_full = ref.encode(N, K, data)
    case("encode", data.shape, np.array_equal(full, want_full), first, median,
         lambda: host.erasure.encode(data))

    worst = tuple(range(N - K, N))  # every data shard lost
    for b in batches:
        mats = rng.integers(0, 256, (b, K, length), dtype=np.uint8)
        fulls = np.stack([ref.encode(N, K, m) for m in mats])
        want_roots = [ref.merkle_root(f) for f in fulls]
        patterns = {"worst": [worst] * b}
        if b <= 8:
            patterns["mixed"] = [
                tuple(sorted(rng.choice(N, size=K, replace=False).tolist()))
                for _ in range(b)
            ]
        for label, idxs in patterns.items():
            shards = np.stack([f[list(ix)] for f, ix in zip(fulls, idxs)])
            (got, roots, dispatches), first, median = _timed(
                lambda: crypto.decode_recheck_batch(np.asarray(idxs), shards),
                repeats,
            )
            equal = np.array_equal(got, mats) and all(
                r.tobytes() == w for r, w in zip(roots, want_roots)
            )
            # and against the reference's own decode of the first matrix
            d0, r0 = ref.decode_recheck(N, K, idxs[0], shards[0])
            equal = equal and np.array_equal(got[0], d0) and (
                roots[0].tobytes() == r0
            )
            case(f"decode_recheck.{label}.dispatches{dispatches}",
                 shards.shape, equal, first, median,
                 lambda: host.decode_recheck_batch(np.asarray(idxs), shards))

    # 256 branch proofs of L-byte leaves, one of them false
    merkle = XlaMerkle()
    merkle.HOST_FLOOR_VERIFY = 0  # this object's alone: the floor crossed by hand
    b = 256
    shard_sets = [want_full] + [
        ref.encode(N, K, rng.integers(0, 256, (K, length), dtype=np.uint8))
        for _ in range(b // N - 1)
    ]
    roots, leaves, branches, indices = [], [], [], []
    for shards in shard_sets:
        levels = ref.merkle_levels(shards)
        for j in range(N):
            roots.append(np.frombuffer(levels[-1][0], dtype=np.uint8))
            leaves.append(shards[j])
            branches.append(np.frombuffer(
                b"".join(ref.merkle_branch(levels, j)), dtype=np.uint8
            ).reshape(-1, 32))
            indices.append(j)
    leaves_arr = np.stack(leaves)
    leaves_arr[7, 100] ^= 1
    want = [
        ref.verify_branch(bytes(r), bytes(lf), [bytes(s) for s in br], j)
        for r, lf, br, j in zip(roots, leaves_arr, branches, indices)
    ]
    args = (np.stack(roots), leaves_arr, np.stack(branches),
            np.asarray(indices))
    got, first, median = _timed(lambda: merkle.verify_batch(*args), repeats)
    equal = [bool(x) for x in got] == want and want.count(False) == 1
    case("verify_batch", leaves_arr.shape, equal, first, median,
         lambda: host.merkle.verify_batch(*args))

    return {"cases": cases, "equal": all(c["equal"] for c in cases)}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from benchmarks import run

    try:
        device = run.require_chip(1)
    except run.NoChip as exc:
        print(f"[kernels] {exc}", file=sys.stderr)
        return run.EXIT_NO_CHIP
    from cleisthenes_tpu.ops import placement

    result = check_kernels(LENGTH, args.seed)
    result["device"] = device
    result["placement"] = placement.snapshot()
    print(json.dumps(result), flush=True)
    return 0 if result["equal"] else 1


if __name__ == "__main__":
    sys.exit(main())
