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

Each row is hashed by OpenSSL's streaming SHA-256 where libcrypto has
it, and a call with enough 64-byte blocks is split over threads inside
the kernel; ``hash_tally`` says what the calls since its last reset
hashed and by which path.
"""

from __future__ import annotations

import functools
import hashlib
import io
import itertools
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from cleisthenes_tpu.native.build import load_sha256


# The kernel's paths, by the number native/sha256rows.cpp's
# ``sha256_path()`` returns; ``hashlib`` is this module's own loop,
# taken where the native library did not build or load.
PATHS = ("builtin", "openssl_oneshot", "openssl_streaming")

# What ``sha256_rows`` hashed since the last reset: calls, rows, 64-byte
# compression blocks (padding included), calls the kernel split over
# threads, and the path the last call took.  Read by tests and
# tools/hashbench.py.
_TALLY_FIELDS = ("calls", "rows", "blocks", "threaded_calls")
_tally: Dict[str, Union[int, str]] = dict.fromkeys(_TALLY_FIELDS, 0)
_tally["path"] = ""


def hash_tally() -> Dict[str, Union[int, str]]:
    """{calls, rows, blocks, threaded_calls, path} since the last
    ``reset_hash_tally``; ``path`` is one of ``PATHS`` or ``hashlib``
    ("" before the first call)."""
    return dict(_tally)


def reset_hash_tally() -> None:
    for key in _TALLY_FIELDS:
        _tally[key] = 0
    _tally["path"] = ""


def _count(m: int, blocks: int, threads: int, path: str) -> None:
    _tally["calls"] += 1
    _tally["rows"] += m
    _tally["blocks"] += blocks
    _tally["threaded_calls"] += threads > 1
    _tally["path"] = path


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
    # SHA-256 pads a message of L bytes to (L + 72) // 64 blocks
    if lens32 is None:
        blocks = m * ((stride + 72) // 64)
    else:
        blocks = int(((lens32.astype(np.int64) + 72) // 64).sum())
    lib = load_sha256()
    if lib is not None:
        if lens32 is None:
            threads = lib.sha256_rows_fixed(
                rows.ctypes.data, m, stride, stride, out.ctypes.data, 0
            )
        else:
            threads = lib.sha256_rows(
                rows.ctypes.data, m, stride, lens32.ctypes.data,
                out.ctypes.data, 0,
            )
        _count(m, blocks, threads, PATHS[lib.sha256_path()])
        return out
    _count(m, blocks, 1, "hashlib")
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
    "PATHS",
    "sha256_rows",
    "hash_tally",
    "reset_hash_tally",
    "ints_to_be_rows",
    "be_rows_to_ints",
]
