"""cProfile of one lockstep N=128 epoch — where does bba_s go?

bba_s was ~3/4 of the N=128 lockstep epoch at the last look (PERF.md,
"History, unverified"); the north star wants the whole epoch under
1 s.  This attributes the gap: device wait (XLA dispatch/transfer
frames) vs host-side marshalling (item assembly, limb packing, CP
hashing, nonce draws) — so the next optimization targets the real
cost, not the assumed one.  One process, no children: with backend
'tpu' on a chip machine this process owns the chip.

Usage:  python tools/profile_spmd.py [n] [batch] [backend]
"""

from __future__ import annotations

import cProfile
import io
import os
import pstats
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import benchlock  # noqa: E402


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000
    backend = sys.argv[3] if len(sys.argv) > 3 else "tpu"
    with benchlock.hold("profile_spmd"):
        import numpy as np

        from cleisthenes_tpu.protocol.spmd import LockstepCluster

        cluster = LockstepCluster(
            n=n, batch_size=batch, crypto_backend=backend, key_seed=77
        )
        rng = np.random.default_rng(13)
        for _ in range((batch // n) * n * 3):
            tx = rng.integers(0, 256, size=64, dtype=np.uint8).tobytes()
            cluster.submit(tx)
        cluster.run_epoch()  # warm-up / compile
        prof = cProfile.Profile()
        prof.enable()
        s = cluster.run_epoch()
        prof.disable()
        print(f"stats: {s}", file=sys.stderr)
        out = io.StringIO()
        st = pstats.Stats(prof, stream=out)
        st.sort_stats("cumulative").print_stats(45)
        st.sort_stats("tottime").print_stats(35)
        print(out.getvalue())
    return 0


if __name__ == "__main__":
    sys.exit(main())
