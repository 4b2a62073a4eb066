"""The control of ``correct``, on the chip at the cell's own size.

    python3 benchmarks/control.py --workload <cell> --seeds 3 --seconds 6

Not part of the benchmark's runs.  One process: for each seed one sound
run, then one run under each fault that benchmarks/faults.py has for
the cell (the timed path broken underneath, one stated guarantee each;
the log's faults only where the configuration has a log).  Every sound run
has to read ``correct`` true and every broken one false; the numbers
compared are printed beside their limits, and they are the readings
PERF.md sets the limits from.  Exits 0 only if all of that held.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_500_000)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--drain-limit", type=float, default=15.0)
    args = ap.parse_args(argv)

    from benchmarks import executors, faults, run, spec

    # a broken commit step never drains: do not wait a minute for it
    executors.DRAIN_LIMIT_S = args.drain_limit
    broken = sorted(faults.for_cell(spec.load_cell(args.workload)).items())
    ok = True
    try:
        for i in range(args.seeds):
            seed = args.first_seed + i
            for name, fault in [("sound", None)] + broken:
                result = run.run_cell(
                    args.workload, seed, args.seconds, False, fault=fault
                )
                held = result["correct"] == (fault is None)
                ok = ok and held
                readings = {
                    k: v["value"] for k, v in result["compared"].items()
                }
                print("[control] " + json.dumps({
                    "workload": args.workload, "seed": seed, "run": name,
                    "correct": result["correct"],
                    "as_expected": held, "attempted": result["attempted"],
                    "failed": result["failed"], "compared": readings,
                }), flush=True)
    except run.NoChip as exc:
        print(f"[control] {exc}", file=sys.stderr)
        return run.EXIT_NO_CHIP
    print(f"[control] all as expected: {ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
