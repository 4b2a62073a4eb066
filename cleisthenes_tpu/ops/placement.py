"""Placement tally: which side of the ops/ seam ran each batch.

Under ``crypto_backend="tpu"`` every batched entry point in ops/
decides per call whether the XLA kernels or the native host kernels
run the batch (``ModEngine._host_floor``, the ``XlaMerkle`` and
``XlaErasureCoder`` floors).  ``CryptoHub.stats()`` counts dispatches
without saying where they ran, so a run that "used the tpu backend"
could have left the device idle and nobody would know.  This module
counts the decision where it is made: calls, items and bytes per
kernel family, ``device`` or ``host``.  An item is what the family's
floor counts (a shard matrix, a branch proof, an exponentiation), so
it is 43 KB in one family and 32 bytes in another; the bytes are
those of the batch's input arrays as the entry point was handed them,
before any padding: what a roofline or a judgement of a floor needs.
``chip_smoke.py`` reads it.
``batch`` takes the same count and opens the batch's span
(``utils.trace.span``): the count says which side, the span how long.
A device batch that ran sharded over a ``parallel.mesh.CryptoMesh`` of
more than one device is counted under ``mesh_calls`` / ``mesh_items``
/ ``mesh_bytes`` as well, so a run can prove that its mesh was in effect.

Only the 'tpu' backend's entry points report here: the 'cpu'/'cpp'
backends have no decision to make.
"""

from __future__ import annotations

import threading
from typing import Dict

from cleisthenes_tpu.utils import trace

_FIELDS = (
    "device_calls", "device_items", "device_bytes",
    "host_calls", "host_items", "host_bytes",
    "mesh_calls", "mesh_items", "mesh_bytes",
)
_lock = threading.Lock()
_counts: Dict[str, Dict[str, int]] = {}


def note(
    family: str, on_device: bool, items: int, mesh=None, nbytes: int = 0
) -> None:
    """One batch of ``items`` in ``family``, ``nbytes`` of input
    arrays, ran on the device (or, with ``on_device`` false, was routed
    to the host kernels); ``mesh`` is the ``CryptoMesh`` a device batch
    was sharded over, if any."""
    side = "device" if on_device else "host"
    sharded = on_device and mesh is not None and mesh.n_devices > 1
    with _lock:
        row = _counts.setdefault(family, dict.fromkeys(_FIELDS, 0))
        row[side + "_calls"] += 1
        row[side + "_items"] += int(items)
        row[side + "_bytes"] += int(nbytes)
        if sharded:
            row["mesh_calls"] += 1
            row["mesh_items"] += int(items)
            row["mesh_bytes"] += int(nbytes)


def batch(
    family: str, on_device: bool, items: int, mesh=None, nbytes: int = 0
):
    """``note`` plus the ``ops/<family>`` span around the batch:
    ``with placement.batch(...):`` holds its pack / device / unpack
    (or host) children."""
    note(family, on_device, items, mesh, nbytes)
    return trace.span(
        "ops", family, items=items, bytes=nbytes, on_device=on_device
    )


def snapshot() -> Dict[str, Dict[str, int]]:
    """{family: {device_calls, device_items, device_bytes, host_calls,
    host_items, host_bytes, mesh_calls, mesh_items, mesh_bytes}} since
    the last ``reset()``, families in name order."""
    with _lock:
        return {fam: dict(_counts[fam]) for fam in sorted(_counts)}


def reset() -> None:
    with _lock:
        _counts.clear()


__all__ = ["note", "batch", "snapshot", "reset"]
