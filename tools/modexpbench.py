"""modexpbench: 2048-bit exponentiations on the chip and on its host.

    python -m tools.modexpbench [--out FILE] [--repeats 2] [--max-rows 16384]
                                [--device-budget-s 300] [--host-only]

For RFC 3526's MODP group 14 (``ops.modmath.GROUP14``), times:

- the device programs of the (11, 192) limb family (``jit__pow_wide``,
  ``jit__dual_pow_wide``: Pallas kernels on a TPU) through
  ``ModEngine("tpu")`` with its host floor off, at 8 to ``--max-rows``
  rows (powers of two), warm: the best and the median of ``--repeats``
  calls after one that compiles;
- the host kernel (native/modpow_wide.cpp, OpenSSL's ladders) as the
  program calls it, the kernel choosing its threads, at the same sizes,
  and at 1, 2, 4, 8 and 13 threads at 8, 64 and 1,024 rows;
- Python's ``pow()``, on 8 rows.

Bases, exponents (full width mod q, as Shamir shares are) and a few
edge rows (0, 1, p - 1; exponents 0 and q - 1) are drawn from a seed;
a batch of ``rows`` repeats its first ``min(rows, 128)`` of them.  Every
result is compared with ``benchmarks/reference_modexp.py`` byte for
byte, every row; a mismatch exits 1.  Prints one JSON object (ms a
call) and writes it to ``--out`` if given; ``crossover`` is, for each
op, the fewest rows from which the device call beats the host call at
that size and every larger one timed (null: never); sizes past
``--device-budget-s`` of device time are listed as skipped.  Needs a
TPU for the device half; without one (``--host-only``) it times the
host alone.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys
import time
from typing import Callable, Dict, List

from benchmarks import reference_modexp as ref

THREADS = (1, 2, 4, 8, 13)
THREAD_SIZES = (8, 64, 1024)
DISTINCT = 128


def _inputs(seed: int):
    rnd = random.Random(seed)
    cols = [
        [rnd.randrange(ref.P) for _ in range(DISTINCT)],
        [rnd.randrange(ref.Q) for _ in range(DISTINCT)],
        [rnd.randrange(ref.P) for _ in range(DISTINCT)],
        [rnd.randrange(ref.Q) for _ in range(DISTINCT)],
    ]
    cols[0][:3] = [0, 1, ref.P - 1]
    cols[1][:2] = [0, ref.Q - 1]
    cols[3][1:3] = [0, ref.Q - 1]
    return cols


def _tile(col: List[int], rows: int) -> List[int]:
    return [col[i % len(col)] for i in range(rows)]


def _time(call: Callable, repeats: int) -> Dict:
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        call()
        out.append(time.perf_counter() - t0)
    return {
        "best_ms": round(min(out) * 1e3, 3),
        "median_ms": round(statistics.median(out) * 1e3, 3),
    }


def measure(
    repeats: int, max_rows: int, host_only: bool, device_budget_s: float
) -> Dict:
    from cleisthenes_tpu.ops import modmath as mm
    from cleisthenes_tpu.ops import widepow

    ref.check_group()
    if (mm.GROUP14.p, mm.GROUP14.q, mm.GROUP14.g) != (ref.P, ref.Q, ref.G):
        raise SystemExit("the program's MODP-14 group is not RFC 3526's")
    u1, e1, u2, e2 = _inputs(20261018)
    want = {
        "pow": ref.to_rows(ref.pow_rows(u1, e1)),
        "dual_pow": ref.to_rows(ref.dual_pow_rows(u1, e1, u2, e2)),
    }
    width = ref.NBYTES

    def check(op: str, got: List[int], rows: int, where: str) -> None:
        rows_want = want[op]
        for i, v in enumerate(got):
            j = i % DISTINCT
            if v.to_bytes(width, "big") != rows_want[j * width:(j + 1) * width]:
                raise SystemExit(f"{where} {op} at {rows} rows: row {i} "
                                 "differs from the reference")

    def calls(engine, rows: int):
        a, x, b, y = (_tile(c, rows) for c in (u1, e1, u2, e2))
        return {
            "pow": lambda: engine.pow_batch(a, x),
            "dual_pow": lambda: engine.dual_pow_batch(a, x, b, y),
        }

    sizes = [1 << k for k in range(3, max_rows.bit_length()) if 1 << k <= max_rows]
    report: Dict = {
        "cpu_count": os.cpu_count(),
        "affinity": len(os.sched_getaffinity(0)),
        "group": "RFC 3526 MODP-14, 2048-bit p, exponents mod q",
        "host": {}, "host_threads": {}, "python_ms_per_row": None,
        "device": {}, "crossover": {},
    }
    host = mm.ModEngine("cpu", group=mm.GROUP14)
    for rows in sizes:
        row = report["host"].setdefault(str(rows), {})
        for op, call in calls(host, rows).items():
            widepow.reset_modpow_wide_tally()
            check(op, call(), rows, "host kernel")
            tally = widepow.modpow_wide_tally()
            row[op] = dict(
                _time(call, repeats), threads=tally["threads"],
                path=tally["path"],
            )
    for rows in THREAD_SIZES:
        a, x, b, y = (_tile(c, rows) for c in (u1, e1, u2, e2))
        row = report["host_threads"].setdefault(str(rows), {})
        for threads in THREADS:
            for op, call in {
                "pow": lambda: widepow.pow_rows(a, x, ref.P, threads),
                "dual_pow": lambda: widepow.dual_pow_rows(
                    a, x, b, y, ref.P, threads),
            }.items():
                check(op, call(), rows, f"host kernel on {threads} threads")
                row.setdefault(op, {})[str(threads)] = _time(call, repeats)
    t0 = time.perf_counter()
    ref.pow_rows(u1[:8], e1[:8])
    report["python_ms_per_row"] = round((time.perf_counter() - t0) * 1e3 / 8, 3)
    if host_only:
        return report

    import jax

    report["device_kind"] = jax.devices()[0].device_kind
    dev = mm.ModEngine("tpu", group=mm.GROUP14)
    dev.host_delegation = False  # the programs themselves, at every size
    t_device = time.perf_counter()
    for rows in sizes:
        if time.perf_counter() - t_device > device_budget_s:
            report["device_skipped"] = [r for r in sizes if r >= rows]
            break
        row = report["device"].setdefault(str(rows), {})
        for op, call in calls(dev, rows).items():
            t0 = time.perf_counter()
            check(op, call(), rows, "device program")
            row[op] = dict(
                _time(call, repeats),
                first_ms=round((time.perf_counter() - t0) * 1e3, 1),
            )
    timed = [r for r in sizes if str(r) in report["device"]]
    for op in ("pow", "dual_pow"):
        cross = None
        for rows in reversed(timed):
            if (report["device"][str(rows)][op]["best_ms"]
                    >= report["host"][str(rows)][op]["best_ms"]):
                break
            cross = rows
        report["crossover"][op] = cross
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools.modexpbench")
    ap.add_argument("--out", help="also write the JSON object here")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--max-rows", type=int, default=16384)
    ap.add_argument("--device-budget-s", type=float, default=300.0,
                    help="time no larger size once the device half has "
                    "taken this long")
    ap.add_argument("--host-only", action="store_true",
                    help="time the host kernels alone (no accelerator)")
    args = ap.parse_args(argv)
    if not args.host_only:
        import jax

        from cleisthenes_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        if jax.default_backend() != "tpu":
            print(f"no TPU: JAX runs on {jax.default_backend()!r} "
                  "(--host-only times the host alone)", file=sys.stderr)
            return 2
    report = measure(
        args.repeats, args.max_rows, args.host_only, args.device_budget_s
    )
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
