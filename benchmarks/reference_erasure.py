"""The plain reference of the erasure-code mechanism: Reed-Solomon over
GF(2^8), the Merkle forest over the shards, and the payload's framing,
written from their definitions.  It imports nothing of the program
(numpy, hashlib and struct alone), so the program's kernels (bit planes
on the MXU, a SHA-256 scan, native host kernels) can be held to it byte
for byte.  All arithmetic is exact, so every comparison against it is
byte equality and the tolerance is 0.

The definitions, as the docstrings of cleisthenes_tpu/ops/gf256.py,
ops/merkle.py and ops/payload.py state them:

Field        GF(2^8) modulo x^8 + x^4 + x^3 + x^2 + 1 (0x11d), generator
             alpha = 2; products by log / antilog tables.
Code         systematic RS(n, k) by the normalised Vandermonde
             construction: V[i, j] = i^j over the field (points 0..n-1,
             0^0 = 1), A = V * inverse(V[:k]); A's top k rows are the
             identity, so shards 0..k-1 are the data rows themselves and
             shards k..n-1 the parity; any k rows of A are invertible.
Decode       the k surviving rows of A, inverted by Gaussian
             elimination, applied to the k surviving shards.
Merkle tree  leaf digest SHA256(0x00 || shard), node SHA256(0x01 ||
             left || right); the leaf row is padded to the next power of
             two with SHA256("cleisthenes-tpu:empty-leaf"); a branch is
             the sibling path bottom-up.
Framing      payload -> 4-byte big-endian length || payload, zero-padded
             to k rows of a length rounded up to a multiple of 128.
"""

from __future__ import annotations

import functools
import hashlib
import struct
from typing import List, Sequence, Tuple

import numpy as np

POLY = 0x11D
LANE = 128
EMPTY_LEAF = hashlib.sha256(b"cleisthenes-tpu:empty-leaf").digest()


def _tables() -> Tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    return 0 if a == 0 or b == 0 else int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def power(a: int, e: int) -> int:
    out = 1
    for _ in range(e):
        out = mul(out, a)
    return out


def scale(c: int, row: np.ndarray) -> np.ndarray:
    """c * row, element by element, for a uint8 vector."""
    if c == 0:
        return np.zeros_like(row)
    out = EXP[LOG[c] + LOG[row]].astype(np.uint8)
    out[row == 0] = 0
    return out


def mat_apply(a: Sequence[Sequence[int]], x: np.ndarray) -> np.ndarray:
    """(m, k) field matrix times (k, L) bytes -> (m, L) bytes."""
    x = np.asarray(x, dtype=np.uint8)
    out = np.zeros((len(a), x.shape[1]), dtype=np.uint8)
    for i, row in enumerate(a):
        for j, c in enumerate(row):
            out[i] ^= scale(int(c), x[j])
    return out


def mat_mul(a: List[List[int]], b: List[List[int]]) -> List[List[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, c in enumerate(row):
                acc ^= mul(c, b[t][j])
            out[i][j] = acc
    return out


def mat_inv(a: Sequence[Sequence[int]]) -> List[List[int]]:
    """Inverse of a (k, k) field matrix by Gaussian elimination."""
    k = len(a)
    aug = [list(map(int, row)) + [int(i == r) for i in range(k)]
           for r, row in enumerate(a)]
    for col in range(k):
        pivot = next((r for r in range(col, k) if aug[r][col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        aug[col], aug[pivot] = aug[pivot], aug[col]
        p = inv(aug[col][col])
        aug[col] = [mul(p, v) for v in aug[col]]
        for r in range(k):
            f = aug[r][col]
            if r != col and f:
                aug[r] = [v ^ mul(f, w) for v, w in zip(aug[r], aug[col])]
    return [row[k:] for row in aug]


@functools.lru_cache(maxsize=None)
def generator(n: int, k: int) -> Tuple[Tuple[int, ...], ...]:
    """The (n, k) systematic generator: Vandermonde on the points
    0..n-1, normalised so that its top k rows are the identity."""
    v = [[power(i, j) for j in range(k)] for i in range(n)]
    a = mat_mul(v, mat_inv(v[:k]))
    if a[:k] != [[int(i == j) for j in range(k)] for i in range(k)]:
        raise AssertionError("the generator is not systematic")
    return tuple(tuple(row) for row in a)


def encode(n: int, k: int, data: np.ndarray) -> np.ndarray:
    """(k, L) data rows -> (n, L) shards: the data, then the parity."""
    data = np.asarray(data, dtype=np.uint8)
    parity = mat_apply(generator(n, k)[k:], data)
    return np.concatenate([data, parity], axis=0)


def decode(n: int, k: int, indices: Sequence[int],
           shards: np.ndarray) -> np.ndarray:
    """The (k, L) data rows from the k shards at ``indices``."""
    a = generator(n, k)
    rows = [a[int(i)] for i in indices]
    return mat_apply(mat_inv(rows), np.asarray(shards, dtype=np.uint8))


def _leaf(shard: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + shard).digest()


def _node(left: bytes, right: bytes) -> bytes:
    return hashlib.sha256(b"\x01" + left + right).digest()


def merkle_levels(shards: np.ndarray) -> List[List[bytes]]:
    """Every level of the tree over an (n, L) shard set, leaves first,
    the root's level last."""
    row = [_leaf(bytes(s)) for s in np.asarray(shards, dtype=np.uint8)]
    width = 1
    while width < len(row):
        width <<= 1
    row += [EMPTY_LEAF] * (width - len(row))
    levels = [row]
    while len(row) > 1:
        row = [_node(row[i], row[i + 1]) for i in range(0, len(row), 2)]
        levels.append(row)
    return levels


def merkle_root(shards: np.ndarray) -> bytes:
    return merkle_levels(shards)[-1][0]


def merkle_branch(levels: List[List[bytes]], index: int) -> List[bytes]:
    """The sibling path of leaf ``index``, bottom-up, from
    ``merkle_levels``' tree."""
    out = []
    for level in levels[:-1]:
        out.append(level[index ^ 1])
        index >>= 1
    return out


def verify_branch(root: bytes, leaf: bytes, branch: Sequence[bytes],
                  index: int) -> bool:
    cur = _leaf(leaf)
    for sibling in branch:
        cur = _node(sibling, cur) if index & 1 else _node(cur, sibling)
        index >>= 1
    return cur == root


def decode_recheck(n: int, k: int, indices: Sequence[int],
                   shards: np.ndarray) -> Tuple[np.ndarray, bytes]:
    """RBC's delivery check: the data rows, and the Merkle root of the
    shard set they re-encode to."""
    data = decode(n, k, indices, shards)
    return data, merkle_root(encode(n, k, data))


def split_payload(payload: bytes, k: int) -> np.ndarray:
    framed = struct.pack(">I", len(payload)) + payload
    per_shard = -(-len(framed) // k)
    per_shard = -(-per_shard // LANE) * LANE
    buf = np.zeros(k * per_shard, dtype=np.uint8)
    buf[: len(framed)] = np.frombuffer(framed, dtype=np.uint8)
    return buf.reshape(k, per_shard)


def join_payload(data: np.ndarray) -> bytes:
    flat = np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    (length,) = struct.unpack(">I", flat[:4].tobytes())
    if length > flat.size - 4:
        raise ValueError("the framed length exceeds the matrix")
    return flat[4: 4 + length].tobytes()


__all__ = ["generator", "encode", "decode", "decode_recheck", "merkle_root",
           "merkle_levels", "merkle_branch", "verify_branch", "split_payload",
           "join_payload", "mat_inv", "mat_apply"]
