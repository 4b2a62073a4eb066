"""Merkle forest: batched tree build and branch verification.

RBC attaches to every VAL/ECHO a Merkle root h and branch b(j) proving
shard s(j) (reference rbc/request.go:9-13, docs/RBC-EN.md:31-39); after
interpolation the root is recomputed to catch corrupt shards
(docs/RBC-EN.md:37-38).  The network-wide cost is N^2 log N hashes per
epoch (docs/HONEYBADGER-EN.md:96) — all independent, so both the build
(one tree per validator's proposal) and the verify (N branches per
delivered instance) are batched onto the TPU via sha256_xla.

Convention: leaf digest = SHA256(0x00 || shard), interior node =
SHA256(0x01 || left || right) (domain separation against second-
preimage splices); leaf sets pad to the next power of two with a fixed
sentinel digest.
"""

from __future__ import annotations

import abc
import dataclasses
import hashlib
from typing import List, Sequence

import numpy as np

from cleisthenes_tpu.ops import placement
from cleisthenes_tpu.parallel.mesh import host_array
from cleisthenes_tpu.utils import trace

_LEAF_PREFIX = b"\x00"
_NODE_PREFIX = b"\x01"
_EMPTY_LEAF_DIGEST = hashlib.sha256(b"cleisthenes-tpu:empty-leaf").digest()


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclasses.dataclass
class MerkleTree:
    """A built tree: levels[0] is the (padded) leaf-digest row, levels[-1]
    is the single root digest.  All rows are (width, 32) uint8."""

    levels: List[np.ndarray]
    n_leaves: int

    @property
    def root(self) -> bytes:
        return self.levels[-1][0].tobytes()

    def branch(self, index: int) -> List[bytes]:
        """Sibling path for leaf ``index``, bottom-up
        (the b(j) of reference rbc/request.go:11)."""
        if not (0 <= index < self.n_leaves):
            raise IndexError(index)
        out = []
        for level in self.levels[:-1]:
            out.append(level[index ^ 1].tobytes())
            index >>= 1
        return out

    @property
    def depth(self) -> int:
        return len(self.levels) - 1


class MerkleBackend(abc.ABC):
    """Batched tree building + branch verification."""

    @abc.abstractmethod
    def _hash_batch(self, msgs: np.ndarray) -> np.ndarray:
        """(B, L) uint8 -> (B, 32) uint8."""

    # -- building ----------------------------------------------------

    def build(self, shards: np.ndarray) -> MerkleTree:
        """(N, L) uint8 shard matrix -> tree over N leaves."""
        return self.build_batch(shards[None])[0]

    def build_batch(self, shards: np.ndarray) -> List[MerkleTree]:
        """(B, N, L) -> B trees, all leaf hashing/level hashing batched."""
        b, n, l = shards.shape
        p = _next_pow2(n)
        prefixed = np.concatenate(
            [
                np.zeros((b * n, 1), dtype=np.uint8),
                shards.reshape(b * n, l),
            ],
            axis=1,
        )
        leaf_dig = self._hash_batch(prefixed).reshape(b, n, 32)
        if p != n:
            pad = np.broadcast_to(
                np.frombuffer(_EMPTY_LEAF_DIGEST, dtype=np.uint8), (b, p - n, 32)
            )
            leaf_dig = np.concatenate([leaf_dig, pad], axis=1)
        levels = [leaf_dig]
        width = p
        while width > 1:
            cur = levels[-1]  # (b, width, 32)
            pairs = cur.reshape(b, width // 2, 64)
            msgs = np.concatenate(
                [
                    np.ones((b * (width // 2), 1), dtype=np.uint8),
                    pairs.reshape(b * (width // 2), 64),
                ],
                axis=1,
            )
            levels.append(self._hash_batch(msgs).reshape(b, width // 2, 32))
            width //= 2
        return [
            MerkleTree([lvl[i] for lvl in levels], n_leaves=n) for i in range(b)
        ]

    # -- verification ------------------------------------------------

    def verify_branch(
        self, root: bytes, leaf: bytes, branch: Sequence[bytes], index: int
    ) -> bool:
        """One proof, pure hashlib: a scalar verify is a handful of
        SHA-256 calls — array assembly (let alone a device dispatch)
        costs more than the hashing.  Batch waves use verify_batch."""
        cur = hashlib.sha256(_LEAF_PREFIX + leaf).digest()
        idx = index
        for sib in branch:
            if idx & 1:
                cur = hashlib.sha256(_NODE_PREFIX + sib + cur).digest()
            else:
                cur = hashlib.sha256(_NODE_PREFIX + cur + sib).digest()
            idx >>= 1
        return cur == root

    def verify_batch(
        self,
        roots: np.ndarray,
        leaves: np.ndarray,
        branches: np.ndarray,
        indices: np.ndarray,
    ) -> np.ndarray:
        """Verify B branches at once.

        roots (B, 32), leaves (B, L) raw shard bytes, branches
        (B, D, 32) sibling paths bottom-up, indices (B,) leaf positions
        -> (B,) bool.  The whole thing is D+1 batched hash dispatches.
        """
        b, l = leaves.shape
        d = branches.shape[1]
        prefixed = np.concatenate(
            [np.zeros((b, 1), dtype=np.uint8), leaves], axis=1
        )
        cur = self._hash_batch(prefixed)  # (B, 32)
        idx = np.asarray(indices).copy()
        for lvl in range(d):
            sib = branches[:, lvl]
            bit = (idx & 1).astype(bool)[:, None]
            left = np.where(bit, sib, cur)
            right = np.where(bit, cur, sib)
            msgs = np.concatenate(
                [np.ones((b, 1), dtype=np.uint8), left, right], axis=1
            )
            cur = self._hash_batch(msgs)
            idx >>= 1
        return (cur == roots).all(axis=1)


class CpuMerkle(MerkleBackend):
    """Host backend: one native batched-SHA crossing per level
    (ops/hashrows; identical digests to the old hashlib loop)."""

    def _hash_batch(self, msgs: np.ndarray) -> np.ndarray:
        from cleisthenes_tpu.ops.hashrows import sha256_rows

        return sha256_rows(msgs)


class XlaMerkle(MerkleBackend):
    """Batched SHA-256 Merkle forest on TPU.

    ``build_batch`` and ``verify_batch`` are overridden with fully
    device-resident jitted kernels: every tree level's hashing is part
    of ONE XLA program (the base class would round-trip host<->device
    per level).  The batch axis is padded to the next power of two
    (min 8) so each (bucket, length) pair compiles exactly once.

    With a ``parallel.mesh.CryptoMesh``, the batch axis shards over
    EVERY mesh device flat (``P(('v','l'))``): hashing is sequential
    within a message but independent across the batch, so trees and
    branch proofs scatter across chips with zero collectives.
    """

    # Below these batch sizes (branch proofs / hashed messages, and
    # forest leaves) the job runs on the native host hasher: small
    # jobs stay on host, batch waves run on device.  An N=16 live
    # epoch's whole merkle load therefore stays native, while the
    # N>=128 crypto-plane waves (16k+ items) take the device path.
    # The floors count items and are blind to their width: at
    # 250-byte transactions (N=16, B=16,384) a wave is 256 proofs of
    # 43 KB leaves, 11 MB of SHA-256, and stays on the host because
    # 256 < 8,192.  Measured there on one v5e chip (PERF.md section 6,
    # PR 34), keeping it on the host is right: 8.5 ms on the native
    # hasher against 72 ms through verify_branches, whose scan walks a
    # leaf's 679 blocks one after another whatever the batch.  Both
    # values are carried over from an earlier attachment of the chip
    # and no crossover in items was searched for (ops.placement counts
    # which side each batch took, in items and in bytes).
    HOST_FLOOR_VERIFY = 8192
    HOST_FLOOR_BUILD_LEAVES = 16384

    def __init__(self, mesh=None):
        self._mesh = mesh
        self._host = CpuMerkle()

    def _bucket(self, b: int) -> int:
        import math

        bucket = 8
        while bucket < b:
            bucket <<= 1
        if self._mesh is not None:
            # padded batch must divide across the flat device count;
            # lcm keeps the power-of-two compile-bucketing AND handles
            # non-power-of-two meshes (e.g. (3, 2))
            bucket = math.lcm(bucket, self._mesh.n_devices)
        return bucket

    def _put(self, x):
        import jax.numpy as jnp

        if self._mesh is None:
            return jnp.asarray(x)
        # the host array itself: each device is sent its own rows
        return self._mesh.put_flat(x)[0]

    def _fetch(self, out) -> np.ndarray:
        return host_array(self._mesh, out)

    def _hash_batch(self, msgs: np.ndarray) -> np.ndarray:
        from cleisthenes_tpu.ops.sha256_xla import sha256_batch

        b = msgs.shape[0]
        if b < self.HOST_FLOOR_VERIFY:
            # also covers the base-class single-tree build(): a
            # 16-leaf tree would be ~5 per-level device dispatches
            with placement.batch(
                "sha256.hash_batch", False, b, nbytes=msgs.nbytes
            ), trace.span("ops", "host"):
                return self._host._hash_batch(msgs)
        with placement.batch(
            "sha256.hash_batch", True, b, self._mesh, nbytes=msgs.nbytes
        ):
            with trace.span("ops", "pack"):
                bucket = self._bucket(b)
                if bucket != b:
                    msgs = np.concatenate([
                        msgs,
                        np.zeros(
                            (bucket - b, msgs.shape[1]), dtype=np.uint8
                        ),
                    ])
            with trace.span("ops", "device", program="sha256_batch"):
                return self._fetch(sha256_batch(self._put(msgs)))[:b]

    def build_batch(self, shards: np.ndarray) -> List[MerkleTree]:
        from cleisthenes_tpu.ops.sha256_xla import build_forest

        b, n, _ = shards.shape
        if b * n < self.HOST_FLOOR_BUILD_LEAVES:
            with placement.batch(
                "merkle.build_forest", False, b * n, nbytes=shards.nbytes
            ), trace.span("ops", "host"):
                return self._host.build_batch(shards)
        with placement.batch(
            "merkle.build_forest", True, b * n, self._mesh,
            nbytes=shards.nbytes,
        ):
            with trace.span("ops", "pack"):
                bucket = self._bucket(b)
                if bucket != b:
                    shards = np.concatenate([
                        shards,
                        np.zeros(
                            (bucket - b,) + shards.shape[1:], np.uint8
                        ),
                    ])
            with trace.span("ops", "device", program="build_forest"):
                # (bucket, 2p-1, 32): the whole forest in one transfer
                forest = self._fetch(build_forest(self._put(shards)))
            with trace.span("ops", "unpack"):
                p = _next_pow2(n)
                levels = []
                off, width = 0, p
                while width >= 1:
                    levels.append(forest[:, off : off + width])
                    off += width
                    width //= 2
                return [
                    MerkleTree([lvl[i] for lvl in levels], n_leaves=n)
                    for i in range(b)
                ]

    def verify_batch(
        self,
        roots: np.ndarray,
        leaves: np.ndarray,
        branches: np.ndarray,
        indices: np.ndarray,
    ) -> np.ndarray:
        from cleisthenes_tpu.ops.sha256_xla import verify_branches

        b = leaves.shape[0]
        # the bytes hashed: each proof's leaf and its path of siblings
        nbytes = leaves.nbytes + branches.nbytes
        if b < self.HOST_FLOOR_VERIFY:
            with placement.batch(
                "merkle.verify_branches", False, b, nbytes=nbytes
            ), trace.span("ops", "host"):
                return self._host.verify_batch(
                    roots, leaves, branches, indices
                )
        bucket = self._bucket(b)

        def pad(a):
            if bucket == b:
                return a
            reps = np.repeat(a[:1], bucket - b, axis=0)
            return np.concatenate([a, reps])

        with placement.batch(
            "merkle.verify_branches", True, b, self._mesh, nbytes=nbytes
        ):
            with trace.span("ops", "pack"):
                columns = (
                    pad(np.ascontiguousarray(roots, dtype=np.uint8)),
                    pad(np.ascontiguousarray(leaves, dtype=np.uint8)),
                    pad(np.ascontiguousarray(branches, dtype=np.uint8)),
                    pad(np.asarray(indices, dtype=np.uint32)),
                )
            with trace.span("ops", "device", program="verify_branches"):
                ok = verify_branches(*(self._put(c) for c in columns))
                return self._fetch(ok)[:b]


def make_merkle(backend: str, mesh=None) -> MerkleBackend:
    if backend == "cpu":
        return CpuMerkle()
    if backend == "tpu":
        return XlaMerkle(mesh=mesh)
    raise ValueError(f"unknown merkle backend {backend!r}")


__all__ = [
    "MerkleTree",
    "MerkleBackend",
    "CpuMerkle",
    "XlaMerkle",
    "make_merkle",
]
