"""Batched host-side SHA-256: one native call per wave.

The lockstep executor and the live hub both end every crypto wave by
hashing one short transcript per share (CP challenges) or per Merkle
node — at N=128 ~72k CP transcripts an epoch; as hashlib calls the
Python call overhead dwarfed the compression work.  ``sha256_rows``
hashes a whole (m, stride) row-matrix in one ctypes crossing via
native/sha256rows.cpp, degrading to a hashlib loop when the toolchain
is unavailable (identical digests either way — the native kernel is
plain FIPS 180-4, selftested at load).  The transcript rows are filled
from byte columns (ops.tpke._cp_digest_rows): the lockstep waves'
columns are the device's own output bytes; ``ints_to_be_rows`` /
``be_rows_to_ints`` are the crossing for what is a Python int (the
list entry points' values, the combine's memo keys and Lagrange
terms).
"""

from __future__ import annotations

import functools
import hashlib
import io
import itertools
from typing import List, Optional, Sequence

import numpy as np

from cleisthenes_tpu.native.build import load_sha256


def sha256_rows(
    rows: np.ndarray, lens: Optional[np.ndarray] = None
) -> np.ndarray:
    """Digest each row of a (m, stride) uint8 matrix -> (m, 32) uint8.

    ``lens`` gives per-row message lengths (defaults to the full
    stride for every row)."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    if rows.ndim != 2:
        raise ValueError(f"rows must be 2-D, got shape {rows.shape}")
    m, stride = rows.shape
    out = np.empty((m, 32), dtype=np.uint8)
    if m == 0:
        return out
    lens32 = None
    if lens is not None:
        lens32 = np.ascontiguousarray(lens, dtype=np.int32)
        if lens32.shape != (m,):
            raise ValueError("lens must be (m,)")
        if int(lens32.min()) < 0 or int(lens32.max()) > stride:
            # the native kernel casts straight to size_t: an
            # out-of-range length would read past the row (and the
            # fallback would silently truncate — reject in both)
            raise ValueError("lens values must be in [0, stride]")
    lib = load_sha256()
    if lib is not None:
        if lens32 is None:
            lib.sha256_rows_fixed(
                rows.ctypes.data, m, stride, stride, out.ctypes.data
            )
        else:
            lib.sha256_rows(
                rows.ctypes.data, m, stride, lens32.ctypes.data,
                out.ctypes.data,
            )
        return out
    # degraded path: identical digests, one hashlib call per row
    if lens32 is None:
        for i in range(m):
            out[i] = np.frombuffer(
                hashlib.sha256(rows[i].tobytes()).digest(), dtype=np.uint8
            )
    else:
        for i in range(m):
            out[i] = np.frombuffer(
                hashlib.sha256(rows[i, : int(lens32[i])].tobytes()).digest(),
                dtype=np.uint8,
            )
    return out


def ints_to_be_rows(values: Sequence[int], nbytes: int) -> np.ndarray:
    """(m, nbytes) big-endian byte matrix from Python ints — the
    transcript field encoder (same bytes as int.to_bytes per item)."""
    # one join + one frombuffer for the whole column: per-item
    # frombuffer assignments were a top-5 profile line at N=128
    buf = b"".join([v.to_bytes(nbytes, "big") for v in values])
    return (
        np.frombuffer(buf, dtype=np.uint8).reshape(len(values), nbytes).copy()
    )


def be_rows_to_ints(rows: np.ndarray) -> List[int]:
    """``ints_to_be_rows``'s inverse: one Python int per row of a
    (m, nbytes) big-endian byte matrix — read and converted without a
    Python-level step per row (the slicing comprehension this replaces
    cost as much again as ``from_bytes``)."""
    width = rows.shape[1]
    if width == 0:
        return [0] * len(rows)
    buf = io.BytesIO(np.ascontiguousarray(rows, dtype=np.uint8).tobytes())
    return list(
        map(
            int.from_bytes,
            iter(functools.partial(buf.read, width), b""),
            itertools.repeat("big"),
        )
    )


__all__ = [
    "sha256_rows",
    "ints_to_be_rows",
    "be_rows_to_ints",
]
