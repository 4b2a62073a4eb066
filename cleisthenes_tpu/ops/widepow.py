"""Batched host exponentiation for groups wider than 256 bits.

A 2048-bit group (``modmath.GROUP14``, RFC 3526's MODP-14) puts every
share issue, Chaum-Pedersen check and Lagrange combine on 2048-bit
exponentiations with 2047-bit exponents: 15.7 ms each through Python's
``pow()`` on the 13-core host of a v5e chip, 2.3 ms through OpenSSL on
one of its cores (PERF.md section 6).  ``pow_rows`` and
``dual_pow_rows`` hand a whole batch to native/modpow_wide.cpp
(OpenSSL's BN Montgomery ladders, the batch split over cores inside
the call) and fall back to ``pow()`` where the
library does not build or libcrypto is missing: the same numbers
either way.  ``modpow_wide_tally`` says what the calls since its last
reset ran and by which path.  ``modmath.ModEngine``'s 'cpu' engine of
a wide group calls these; the 256-bit groups keep
native/modpow256.cpp.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Union

import numpy as np

from cleisthenes_tpu.native.build import load_modpow_wide
from cleisthenes_tpu.ops.hashrows import be_rows_to_ints, ints_to_be_rows

# The paths a call can take: the native kernel, or this module's loop
PATHS = ("openssl", "python")

# Since the last reset: calls, rows (a dual_pow row is one), calls the
# kernel split over threads, the most threads a call ran on, and the
# path of the last call.  Read by tests and tools/modexpbench.py.
_TALLY_FIELDS = ("calls", "rows", "threaded_calls", "threads")
_tally: Dict[str, Union[int, str]] = dict.fromkeys(_TALLY_FIELDS, 0)
_tally["path"] = ""


def modpow_wide_tally() -> Dict[str, Union[int, str]]:
    """{calls, rows, threaded_calls, threads, path} since the last
    ``reset_modpow_wide_tally``; ``path`` is one of ``PATHS`` ("" before
    the first call)."""
    return dict(_tally)


def reset_modpow_wide_tally() -> None:
    for key in _TALLY_FIELDS:
        _tally[key] = 0
    _tally["path"] = ""


def _count(m: int, threads: int, path: str) -> None:
    _tally["calls"] += 1
    _tally["rows"] += m
    _tally["threaded_calls"] += threads > 1
    _tally["threads"] = max(int(_tally["threads"]), threads)
    _tally["path"] = path


def _exp_width(*columns: Sequence[int]) -> int:
    """Bytes of the widest exponent (a negative one raises as the rows
    are made, as the 256-bit kernel's do)."""
    return max(1, (max(max(col) for col in columns).bit_length() + 7) // 8)


def pow_rows(
    bases: Sequence[int], exps: Sequence[int], modulus: int, threads: int = 0
) -> List[int]:
    """[b ** e % modulus]; ``threads`` 0 lets the kernel choose."""
    m = len(bases)
    if m == 0:
        return []
    lib = load_modpow_wide() if modulus % 2 else None
    if lib is None:
        _count(m, 1, "python")
        return [pow(b, e, modulus) for b, e in zip(bases, exps)]
    width = (modulus.bit_length() + 7) // 8
    ew = _exp_width(exps)
    cols = (
        ints_to_be_rows([b % modulus for b in bases], width),
        ints_to_be_rows(exps, ew),
    )
    return _run(
        lib.modpow_wide_batch, cols, ew, modulus, width, m, threads
    )


def dual_pow_rows(
    u1: Sequence[int],
    e1: Sequence[int],
    u2: Sequence[int],
    e2: Sequence[int],
    modulus: int,
    threads: int = 0,
) -> List[int]:
    """[u1 ** e1 * u2 ** e2 % modulus] (public exponents: a
    Chaum-Pedersen check's response and challenge)."""
    m = len(u1)
    if m == 0:
        return []
    lib = load_modpow_wide() if modulus % 2 else None
    if lib is None:
        _count(m, 1, "python")
        return [
            pow(a, x, modulus) * pow(b, y, modulus) % modulus
            for a, x, b, y in zip(u1, e1, u2, e2)
        ]
    width = (modulus.bit_length() + 7) // 8
    ew = _exp_width(e1, e2)
    cols = (
        ints_to_be_rows([u % modulus for u in u1], width),
        ints_to_be_rows(e1, ew),
        ints_to_be_rows([u % modulus for u in u2], width),
        ints_to_be_rows(e2, ew),
    )
    return _run(
        lib.dualpow_wide_batch, cols, ew, modulus, width, m, threads
    )


def _run(call, cols, ew, modulus, width, m, threads) -> List[int]:
    mod = np.frombuffer(modulus.to_bytes(width, "big"), dtype=np.uint8)
    out = np.empty((m, width), dtype=np.uint8)
    ran = call(
        *(c.ctypes.data for c in cols), ew, mod.ctypes.data, width,
        out.ctypes.data, m, threads,
    )
    if ran < 1:
        raise RuntimeError("native wide modexp failed inside OpenSSL")
    _count(m, ran, "openssl")
    return be_rows_to_ints(out)


__all__ = [
    "PATHS",
    "pow_rows",
    "dual_pow_rows",
    "modpow_wide_tally",
    "reset_modpow_wide_tally",
]
