"""Wave-flush ordering is hash-seed independent (ISSUE 7).

Batched crypto runs at the transport's quiescence points: one columnar
hub flush per message wave.  A cross-PYTHONHASHSEED subprocess check
holds the wave ordering itself (drain order, wave widths, dispatch
counts) hash-seed independent; what a seeded run COMMITS is pinned in
tests/test_seeded_pins.py.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# Prints one line digesting the ledger bytes AND the wave structure
# itself: per-run hub wave widths, dispatch count, and column item
# totals.  Two PYTHONHASHSEED values must produce identical lines —
# hash-order iteration anywhere in the drain/dispatch path would show
# up as different wave compositions even when ledgers converge.
_WAVE_DRIVER = r"""
import hashlib
from cleisthenes_tpu.config import Config
from cleisthenes_tpu.core.ledger import encode_batch_body
from cleisthenes_tpu.protocol.cluster import SimulatedCluster

cluster = SimulatedCluster(
    config=Config(n=4, batch_size=8, seed=2026),
    seed=2026,
    key_seed=3,
)
for i in range(24):
    cluster.submit(b"wave-hs-%04d" % i)
cluster.run_epochs()
depth = cluster.assert_agreement()
assert depth >= 2, f"want >=2 committed epochs, got {depth}"
h = hashlib.sha256()
for nid in cluster.ids:
    for epoch, batch in enumerate(cluster.nodes[nid].committed_batches):
        h.update(encode_batch_body(epoch, batch))
hub = cluster.nodes[cluster.ids[0]].hub
st = hub.stats()
print(
    "WAVE_DIGEST=%s widths=%s dispatches=%d items=%d/%d/%d"
    % (
        h.hexdigest(),
        ",".join(str(w) for w in hub.wave_widths),
        st["dispatches"],
        st["branch_items"],
        st["decode_items"],
        st["share_items"],
    )
)
"""


def _run_wave_driver(hashseed: str) -> str:
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = hashseed
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _WAVE_DRIVER],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, (
        f"PYTHONHASHSEED={hashseed} wave run failed:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )
    for line in proc.stdout.splitlines():
        if line.startswith("WAVE_DIGEST="):
            return line
    raise AssertionError(f"no wave digest line:\n{proc.stdout}")


def test_wave_ordering_identical_across_hash_seeds():
    a = _run_wave_driver("1")
    b = _run_wave_driver("2")
    assert a == b, (
        "wave composition diverged across PYTHONHASHSEED values:\n"
        f"  {a}\n  {b}\n-> hash-order iteration is leaking into the "
        "hub's drain/dispatch path (see staticcheck DET002/DET003)"
    )
