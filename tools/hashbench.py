"""hashbench: what the host row-hash kernel costs a row, by path and threads.

    python -m tools.hashbench [--out FILE] [--repeats 7]

Hashes random rows of the shapes the program hands
``native/sha256rows.cpp`` — 38-byte keystream counter rows
(``ops/tpke.py::_keystream``), 230-byte Chaum-Pedersen transcripts
(``_cp_digest_rows``) and 43,393-byte Merkle leaves (the paper-width
cell's shards) — through each path the kernel has (OpenSSL's streaming
calls, its one-shot call, the portable loop) at 1, 2, 4, 8, 13 and 16
threads, and through ``ops.hashrows.sha256_rows`` as the program calls
it, the kernel choosing its threads.  Then the sweep the thread floor
is read from: streaming rows of one and of four blocks at growing
batch sizes, on one thread and on 2–13.  Every digest is checked
against ``hashlib``; a mismatch exits 1.

Prints one JSON object (ns a row, the best of ``--repeats`` calls and
their median) and writes it to ``--out`` if given.  Needs no
accelerator: run it on the host whose cores the program uses.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import statistics
import sys
import time
from typing import Dict, List

import numpy as np

from cleisthenes_tpu.native.build import load_sha256
from cleisthenes_tpu.ops import hashrows

THREADS = (1, 2, 4, 8, 13, 16)
# (name, rows, row bytes): one open of a 260 KB proposal, an N=128
# keystream, a lockstep epoch's CP transcripts, one Merkle branch wave
SHAPES = (
    ("keystream_open", 8125, 38),
    ("keystream_n128", 156, 38),
    ("cp_transcripts", 71000, 230),
    ("merkle_leaves", 256, 43393),
)
SWEEP_ROWS = {38: (256, 512, 1024, 2048, 4096, 8192, 16384),
              230: (64, 128, 256, 512, 1024, 2048, 4096)}
SWEEP_THREADS = (1, 2, 4, 8, 13)


def _time(call, repeats: int) -> List[float]:
    call()  # first touch of the pages and of any lazy set-up
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        call()
        out.append(time.perf_counter() - t0)
    return out


def _ns_row(secs: List[float], m: int) -> Dict[str, float]:
    return {
        "best": round(min(secs) * 1e9 / m, 1),
        "median": round(statistics.median(secs) * 1e9 / m, 1),
    }


def _check(rows: np.ndarray, out: np.ndarray) -> None:
    for i in sorted({0, len(rows) // 2, len(rows) - 1}):
        if out[i].tobytes() != hashlib.sha256(rows[i].tobytes()).digest():
            raise SystemExit(f"digest of row {i} differs from hashlib")


def measure(lib, repeats: int) -> dict:
    rng = np.random.default_rng(41)
    report: dict = {
        "cpu_count": os.cpu_count(),
        "affinity": len(os.sched_getaffinity(0)),
        "thread_floor_blocks": lib.sha256_thread_floor_blocks(),
        "paths": {},
        "program": {},
        "sweep": {},
    }
    for cap, name in reversed(list(enumerate(hashrows.PATHS))):
        if lib.sha256_resolve(cap) != cap:
            report["paths"][name] = "unavailable"
            continue
        per_shape = report["paths"].setdefault(name, {})
        for shape, m, width in SHAPES:
            rows = rng.integers(0, 256, size=(m, width), dtype=np.uint8)
            out = np.empty((m, 32), dtype=np.uint8)
            per_shape[shape] = {}
            for t in THREADS:
                secs = _time(
                    lambda: lib.sha256_rows_fixed(
                        rows.ctypes.data, m, width, width, out.ctypes.data, t
                    ),
                    repeats,
                )
                _check(rows, out)
                per_shape[shape][str(t)] = _ns_row(secs, m)
    lib.sha256_resolve(len(hashrows.PATHS) - 1)
    for shape, m, width in SHAPES:
        rows = rng.integers(0, 256, size=(m, width), dtype=np.uint8)
        hashrows.reset_hash_tally()
        secs = _time(lambda: hashrows.sha256_rows(rows), repeats)
        _check(rows, hashrows.sha256_rows(rows))
        out = np.empty((m, 32), dtype=np.uint8)
        report["program"][shape] = dict(
            _ns_row(secs, m),
            threads=lib.sha256_rows_fixed(
                rows.ctypes.data, m, width, width, out.ctypes.data, 0
            ),
            tally=hashrows.hash_tally(),
        )
    for width, sizes in SWEEP_ROWS.items():
        for m in sizes:
            rows = rng.integers(0, 256, size=(m, width), dtype=np.uint8)
            out = np.empty((m, 32), dtype=np.uint8)
            row = {}
            for t in SWEEP_THREADS:
                secs = _time(
                    lambda: lib.sha256_rows_fixed(
                        rows.ctypes.data, m, width, width, out.ctypes.data, t
                    ),
                    repeats,
                )
                row[str(t)] = round(min(secs) * 1e6, 1)
            _check(rows, out)
            blocks = m * ((width + 72) // 64)
            report["sweep"][f"{width}B x {m} ({blocks} blocks), us"] = row
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools.hashbench")
    ap.add_argument("--out", help="also write the JSON object here")
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args(argv)
    lib = load_sha256()
    if lib is None:
        print("native sha256rows kernel did not build or load", file=sys.stderr)
        return 2
    try:
        report = measure(lib, args.repeats)
    finally:
        lib.sha256_resolve(len(hashrows.PATHS) - 1)
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
