"""TPU-native Reed-Solomon codec: GF(2^8) as one MXU matmul.

Design (SURVEY.md §7 step 3): multiplication by a GF(2^8) constant is
GF(2)-linear on bits, so the whole systematic encode
``parity = A_p (*) data`` lifts to ``parity_bits = (G @ data_bits) mod 2``
where G is the (8P x 8K) 0/1 lifting of the parity rows
(gf256.lift_to_bits).  Bytes are unpacked to 8 bit-planes, the matmul
runs on the MXU in bf16 with exact f32 accumulation (every dot is a sum
of <= 8*K <= 2048 zeros/ones, far below 2^24), and the result is
reduced mod 2 and repacked.  Decode is identical with G built from the
inverse of the surviving rows (inverted on host — O(k^3) on an
always-tiny matrix — and cached per erasure pattern).

This replaces the hand-written AVX2 GF kernels the reference leans on
(klauspost/reedsolomon, reference go.mod:10) with something the MXU is
*better* at: at N=128/f=42 an encode is a (672 x 352) @ (352 x L)
matmul — pure systolic-array work, vmappable across all N validators'
RBC instances at once (SURVEY.md §2.2).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cleisthenes_tpu.ops import gf256, placement
from cleisthenes_tpu.ops.backend import ErasureCoder
from cleisthenes_tpu.utils import trace


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(r, L) uint8 -> (8r, L) bf16 bit-planes, LSB-first per byte."""
    r, l = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[:, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(8 * r, l).astype(jnp.bfloat16)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(8r, L) integer 0/1 -> (r, L) uint8."""
    r8, l = bits.shape
    b = bits.reshape(r8 // 8, 8, l).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))[None, :, None]
    return (b * weights).sum(axis=1).astype(jnp.uint8)


def _gf_apply_bits(g_bits: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Apply a lifted GF matrix to byte data: (8m,8k) x (k,L) -> (m,L)."""
    acc = jnp.dot(
        g_bits, _unpack_bits(data), preferred_element_type=jnp.float32
    )
    return _pack_bits(acc.astype(jnp.int32) & 1)


@jax.jit
def _encode_kernel(g_bits: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    parity = _gf_apply_bits(g_bits, data)
    return jnp.concatenate([data, parity], axis=0)


@jax.jit
def _decode_kernel(g_bits: jnp.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    return _gf_apply_bits(g_bits, shards)


# Batched variants: one extra leading axis for the validator/instance
# dimension — all N RBC instances' codec work in a single dispatch.
_encode_kernel_batch = jax.jit(jax.vmap(_encode_kernel, in_axes=(None, 0)))
_decode_kernel_batch = jax.jit(jax.vmap(_decode_kernel, in_axes=(0, 0)))
# Shared-erasure-pattern decode: every instance lost the same shards
# (the common case — e.g. the same f laggards across all N RBCs), so
# one small matrix ships instead of a per-instance stack.
_decode_kernel_shared = jax.jit(jax.vmap(_decode_kernel, in_axes=(None, 0)))


@jax.jit
def _decode_recheck_kernel(g_dec, g_enc, shards):
    """RBC's delivery check in ONE program: interpolate the data
    shards, re-encode the full shard set, and hash the Merkle forest to
    its roots (docs/RBC-EN.md:37-39's decode + root recheck).  Fusing
    the chain keeps the intermediate (B, n, L) shard tensor on device
    and turns the hub's decode path from 3 dispatches into 1."""
    from cleisthenes_tpu.ops.sha256_xla import build_forest

    data = jax.vmap(lambda s: _gf_apply_bits(g_dec, s))(shards)
    full = jax.vmap(
        lambda d: jnp.concatenate([d, _gf_apply_bits(g_enc, d)], axis=0)
    )(data)
    forest = build_forest(full)  # (B, 2p-1, 32); root is the last node
    return data, forest[:, -1]


class XlaErasureCoder(ErasureCoder):
    # A single instance's encode/decode below this byte count (and a
    # batch below four times it) runs on the host numpy path: the
    # single-shot ops (one proposer's VAL encode at 64-byte
    # transactions) are exactly the small case.  Measured above it on
    # one v5e chip, at the (6, 43,392) matrices of 250-byte
    # transactions at N=16, B=16,384 (PERF.md section 6, PR 34; call
    # with transfer and fetch / the native host kernel): one encode
    # 2.2 / 5.2 ms; the fused decode-recheck of 8 matrices 72 / 94 ms
    # and of 256 matrices 209 / 3,237 ms, nearly all of the 72 ms the
    # 679 sequential SHA-256 blocks of a 43 KB leaf (the three-step
    # path, RS on the device and the forest on the host, takes 16 ms
    # for the same 8).  The floor's value itself is carried over from
    # an earlier attachment of the chip and no crossover was searched
    # for below it (ops.placement counts which side each batch took,
    # in items and in bytes).  It holds under a mesh as without one:
    # the floors read the bytes, not the layout.
    HOST_FLOOR_BYTES = 1 << 16

    def __init__(self, n: int, k: int, mesh=None):
        super().__init__(n, k)
        self.matrix = gf256.systematic_rs_matrix(n, k)
        from cleisthenes_tpu.ops.rs_cpu import CpuErasureCoder

        self._host = CpuErasureCoder(n, k)
        self._g_enc = jnp.asarray(
            gf256.lift_to_bits(self.matrix[k:]), dtype=jnp.bfloat16
        )
        # parallel.mesh.CryptoMesh: batch ops shard (B, k, L) as
        # P('v', None, 'l') — the contraction is over the k axis, so
        # both the instance axis and the shard-length axis partition
        # with zero collectives (SURVEY.md §5.7's length sharding).
        self._mesh = mesh
        # Per-instance cache of lifted decode matrices by erasure
        # pattern (class-level lru_cache would pin instances alive).
        self._decode_bits = functools.lru_cache(maxsize=512)(
            self._decode_bits_impl
        )

    def _put_vl(self, data: np.ndarray):
        """Shard a (B, r, L) batch over the mesh, padding B to the 'v'
        dim and L to the 'l' dim; returns (device_array, b, l)."""
        v, l_dim = self._mesh.shape
        data, b = self._mesh.pad_rows(data, v)
        data, l = self._mesh.pad_cols(data, l_dim)
        # the host array itself: each device is sent its own block
        return self._mesh.put_vl(data), b, l

    def _fetch_vl(self, out, b: int, l: int) -> np.ndarray:
        """A ``_put_vl`` batch's result, padding cut off."""
        return self._mesh.gather(out, "vl")[:b, :, :l]

    def encode(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        assert data.ndim == 2 and data.shape[0] == self.k, data.shape
        if self.n == self.k:
            return data.copy()
        if data.nbytes < self.HOST_FLOOR_BYTES:
            with placement.batch(
                "rs_gf256.encode", False, 1, nbytes=data.nbytes
            ), trace.span("ops", "host"):
                return self._host.encode(data)
        with placement.batch(
            "rs_gf256.encode", True, 1, nbytes=data.nbytes
        ), trace.span("ops", "device", program="_encode_kernel"):
            return np.asarray(_encode_kernel(self._g_enc, jnp.asarray(data)))

    def _decode_bits_impl(self, indices: tuple) -> jnp.ndarray:
        inv = gf256.gf_mat_inv(self.matrix[list(indices)])
        return jnp.asarray(gf256.lift_to_bits(inv), dtype=jnp.bfloat16)

    def _decode_impl(self, indices: tuple, shards: np.ndarray) -> np.ndarray:
        if shards.nbytes < self.HOST_FLOOR_BYTES:
            with placement.batch(
                "rs_gf256.decode", False, 1, nbytes=shards.nbytes
            ), trace.span("ops", "host"):
                return self._host._decode_impl(indices, shards)
        with placement.batch(
            "rs_gf256.decode", True, 1, nbytes=shards.nbytes
        ):
            with trace.span("ops", "pack"):
                g = self._decode_bits(indices)
            with trace.span("ops", "device", program="_decode_kernel"):
                return np.asarray(_decode_kernel(g, jnp.asarray(shards)))

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        assert data.ndim == 3 and data.shape[1] == self.k, data.shape
        if self.n == self.k:
            return data.copy()
        if data.nbytes < 4 * self.HOST_FLOOR_BYTES:
            with placement.batch(
                "rs_gf256.encode_batch", False, len(data), nbytes=data.nbytes
            ), trace.span("ops", "host"):
                return self._host.encode_batch(data)
        with placement.batch(
            "rs_gf256.encode_batch", True, len(data), self._mesh,
            nbytes=data.nbytes,
        ), trace.span("ops", "device", program="_encode_kernel_batch"):
            if self._mesh is None:
                return np.asarray(
                    _encode_kernel_batch(self._g_enc, jnp.asarray(data))
                )
            dev, b, l = self._put_vl(data)
            return self._fetch_vl(
                _encode_kernel_batch(self._g_enc, dev), b, l
            )

    def decode_recheck_batch(self, indices: np.ndarray, shards: np.ndarray):
        """Fused decode + re-encode + Merkle roots, or None when the
        fusion doesn't apply (mesh-sharded runs and mixed erasure
        patterns use the separate batched kernels instead).

        Returns (data (B, k, L), roots (B, 32)).  The batch axis pads
        to a power of two (min 8) so each (bucket, k, L) shape compiles
        once."""
        if self._mesh is not None or self.n == self.k:
            return None
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        if shards.nbytes < 4 * self.HOST_FLOOR_BYTES:
            # tiny job: the 3-step path, whose own floors then tally
            # (and span) each step; this row counts the fusion's
            # refusals, and its span is empty
            with placement.batch(
                "rs_gf256.decode_recheck", False, len(shards),
                nbytes=shards.nbytes,
            ):
                return None
        patterns = [self._normalize_indices(ix) for ix in indices]
        if len(set(patterns)) != 1:
            return None
        with placement.batch(
            "rs_gf256.decode_recheck", True, len(shards),
            nbytes=shards.nbytes,
        ):
            with trace.span("ops", "pack"):
                g = self._decode_bits(patterns[0])
                b = shards.shape[0]
                bucket = 8
                while bucket < b:
                    bucket <<= 1
                if bucket != b:
                    shards = np.concatenate(
                        [shards, np.repeat(shards[:1], bucket - b, axis=0)]
                    )
            with trace.span(
                "ops", "device", program="_decode_recheck_kernel"
            ):
                data, roots = _decode_recheck_kernel(
                    g, self._g_enc, jnp.asarray(shards)
                )
                return np.asarray(data)[:b], np.asarray(roots)[:b]

    def decode_batch(
        self, indices: np.ndarray, shards: np.ndarray
    ) -> np.ndarray:
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        if shards.nbytes < 4 * self.HOST_FLOOR_BYTES:
            with placement.batch(
                "rs_gf256.decode_batch", False, len(shards),
                nbytes=shards.nbytes,
            ), trace.span("ops", "host"):
                return self._host.decode_batch(indices, shards)
        with placement.batch(
            "rs_gf256.decode_batch", True, len(shards), self._mesh,
            nbytes=shards.nbytes,
        ):
            with trace.span("ops", "pack"):
                patterns = [self._normalize_indices(ix) for ix in indices]
            with trace.span("ops", "device", program="_decode_kernel_batch"):
                return self._decode_batch_device(patterns, shards)

    def _decode_batch_device(self, patterns, shards: np.ndarray):
        if len(set(patterns)) == 1:
            g = self._decode_bits(patterns[0])
            if self._mesh is None:
                return np.asarray(
                    _decode_kernel_shared(g, jnp.asarray(shards))
                )
            dev, b, l = self._put_vl(shards)
            return self._fetch_vl(_decode_kernel_shared(g, dev), b, l)
        g = jnp.stack([self._decode_bits(p) for p in patterns])
        if self._mesh is None:
            return np.asarray(_decode_kernel_batch(g, jnp.asarray(shards)))
        dev, b, l = self._put_vl(shards)
        v = self._mesh.shape[0]
        # the per-instance decode matrices shard batch-only: their
        # trailing axes are the contraction dims
        g_np, _ = self._mesh.pad_rows(np.asarray(g), v)
        g_dev = self._mesh.put_v(g_np)
        return self._fetch_vl(_decode_kernel_batch(g_dev, dev), b, l)


__all__ = ["XlaErasureCoder"]
