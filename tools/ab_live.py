"""Quick live-path A/B: measure protocol_n64 before/after a change.

Runs bench.measure_protocol on the cpu backend under the benchlock
(pausing the background sweep so the cores are ours) and prints the
section dict.

Usage:  python tools/ab_live.py [n] [batch] [epochs]
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# host-path measurement: never takes the chip
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import bench  # noqa: E402
from tools import benchlock  # noqa: E402


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    epochs = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    with benchlock.hold("ab_live"):
        out = bench.measure_protocol("cpu", n, batch, epochs)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
