"""Profile the live message-passing path (bench protocol_n64 config).

The N=64/B=1024 epoch's host profile is DIFFUSE (~25 functions x
0.3-1.7 s at the last look).  This driver reproduces the bench
section under cProfile so each candidate change is aimed at the
CURRENT top lines, not memory.

Usage:  JAX_PLATFORMS=cpu python tools/profile_live.py [N] [BATCH]
"""

from __future__ import annotations

import cProfile
import io
import os
import pstats
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# a host-path profile: pin the CPU platform BEFORE anything imports
# jax, so this never takes the chip from the process that owns it
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import bench  # noqa: E402
from tools import benchlock  # noqa: E402


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    with benchlock.hold("profile_live"):
        cfg, net, nodes, _cluster = bench.build_network(
            "cpu", n=n, batch=batch
        )
        rng = np.random.default_rng(13)
        node_ids = sorted(nodes)
        for i in range(batch * 2):
            tx = rng.integers(
                0, 256, size=bench.TX_BYTES, dtype=np.uint8
            ).tobytes()
            nodes[node_ids[i % n]].add_transaction(tx)
        # warm-up epoch
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        # measured epoch under the profiler
        prof = cProfile.Profile()
        t0 = time.perf_counter()
        prof.enable()
        for hb in nodes.values():
            hb.start_epoch()
        net.run()
        prof.disable()
        wall = time.perf_counter() - t0
    print(f"epoch wall: {wall:.2f} s  (n={n}, batch={batch})")
    for sort in ("tottime", "cumulative"):
        buf = io.StringIO()
        ps = pstats.Stats(prof, stream=buf)
        ps.sort_stats(sort).print_stats(30)
        print(f"==== top 30 by {sort} ====")
        # strip the long header boilerplate
        lines = buf.getvalue().splitlines()
        start = next(
            (i for i, ln in enumerate(lines) if "ncalls" in ln), 0
        )
        print("\n".join(lines[start:start + 32]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
