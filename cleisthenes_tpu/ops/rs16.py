"""Reed-Solomon over GF(2^16): rosters past the 256-shard ceiling.

Same systematic construction and the same two backends as the GF(2^8)
codec (ops/rs_cpu.py, ops/rs_xla.py), one field up: shard byte rows of
even length L are L/2 little-endian uint16 symbols, and the XLA path
lifts the generator to a (16n x 16k) 0/1 matrix so the whole transform
is one MXU matmul over 16 bit-planes (dots sum <= 16k ones — exact in
bf16-multiply/f32-accumulate; ops/gf65536.py module docstring).

The reference's lineage cannot express these rosters at all: its codec
dependency hard-caps data+parity shards at 256 (klauspost/reedsolomon,
reference go.mod:10).  N=512 RBC — 512 distinct shard indices — needs
this field.
"""

from __future__ import annotations

import functools

import numpy as np

from cleisthenes_tpu.ops import gf65536 as gf
from cleisthenes_tpu.ops import placement
from cleisthenes_tpu.ops.backend import ErasureCoder
from cleisthenes_tpu.utils import trace


def _to_symbols(x: np.ndarray) -> np.ndarray:
    """(r, L) uint8, L even -> (r, L/2) uint16 little-endian."""
    x = np.ascontiguousarray(x, dtype=np.uint8)
    if x.shape[-1] % 2:
        raise ValueError(
            f"GF(2^16) shards need even byte length, got L={x.shape[-1]}"
        )
    return x.view("<u2")


def _to_bytes(x: np.ndarray) -> np.ndarray:
    """(r, S) uint16 -> (r, 2S) uint8 little-endian."""
    return np.ascontiguousarray(x, dtype="<u2").view(np.uint8)


class Cpu16ErasureCoder(ErasureCoder):
    """Host reference: exp/log-table matmul over uint16 symbols."""

    MAX_N = gf.ORDER

    def __init__(self, n: int, k: int):
        super().__init__(n, k)
        self.matrix = gf.systematic_rs_matrix(n, k)
        self._decode_matrix = functools.lru_cache(maxsize=512)(
            self._decode_matrix_impl
        )

    def encode(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        assert data.ndim == 2 and data.shape[0] == self.k, data.shape
        if self.n == self.k:
            return data.copy()
        syms = _to_symbols(data)
        parity = gf.gf_matmul(self.matrix[self.k :], syms)
        return np.concatenate([data, _to_bytes(parity)], axis=0)

    def _decode_matrix_impl(self, indices: tuple) -> np.ndarray:
        return gf.gf_mat_inv(self.matrix[list(indices)])

    def _decode_impl(self, indices: tuple, shards: np.ndarray) -> np.ndarray:
        return _to_bytes(
            gf.gf_matmul(self._decode_matrix(indices), _to_symbols(shards))
        )


class Xla16ErasureCoder(ErasureCoder):
    """MXU path: lifted (16n x 16k) bit-matmul, batched across
    instances (mirrors ops/rs_xla.XlaErasureCoder)."""

    MAX_N = gf.ORDER

    def __init__(self, n: int, k: int, mesh=None):
        super().__init__(n, k)
        self.mesh = mesh  # accepted for factory symmetry (batch axis
        # sharding rides the same put_flat seam when wired)
        self._cpu = Cpu16ErasureCoder(n, k)
        self.matrix = self._cpu.matrix
        self._g_parity = gf.lift_to_bits(self.matrix[self.k :])
        self._g_decode = functools.lru_cache(maxsize=512)(
            self._g_decode_impl
        )

    def _g_decode_impl(self, indices: tuple) -> np.ndarray:
        return gf.lift_to_bits(gf.gf_mat_inv(self.matrix[list(indices)]))

    # -- single-instance ops (tiny: host path keeps dispatch count
    # down, same policy as the 8-bit XLA coder's host floor) ----------
    def encode(self, data: np.ndarray) -> np.ndarray:
        with placement.batch(
            "rs_gf65536.encode", False, 1, nbytes=np.asarray(data).nbytes
        ), trace.span("ops", "host"):
            return self._cpu.encode(data)

    def _decode_impl(self, indices: tuple, shards: np.ndarray) -> np.ndarray:
        with placement.batch(
            "rs_gf65536.decode", False, 1, nbytes=np.asarray(shards).nbytes
        ), trace.span("ops", "host"):
            return self._cpu._decode_impl(indices, shards)

    # -- batched ops: one lifted matmul for all instances -------------
    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from cleisthenes_tpu.ops.rs16_xla_kernels import encode_kernel_batch

        data = np.ascontiguousarray(data, dtype=np.uint8)
        b, k, L = data.shape
        assert k == self.k
        if self.n == self.k:
            return data.copy()
        with placement.batch(
            "rs_gf65536.encode_batch", True, b, nbytes=data.nbytes
        ):
            with trace.span("ops", "pack"):
                syms = data.view("<u2").reshape(b, k, L // 2)
            with trace.span("ops", "device", program="encode_kernel_batch"):
                out = encode_kernel_batch(
                    jnp.asarray(self._g_parity), jnp.asarray(syms)
                )
                full = np.asarray(out)  # (b, n, L/2) uint16
            with trace.span("ops", "unpack"):
                return np.ascontiguousarray(full.astype("<u2")).view(
                    np.uint8
                ).reshape(b, self.n, L)

    def decode_batch(
        self, indices: np.ndarray, shards: np.ndarray
    ) -> np.ndarray:
        import jax.numpy as jnp

        from cleisthenes_tpu.ops.rs16_xla_kernels import (
            decode_kernel_shared,
        )

        indices = np.asarray(indices)
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        b, k, L = shards.shape
        patterns = {tuple(int(i) for i in row) for row in indices}
        if len(patterns) == 1:
            pat = next(iter(patterns))
            self._normalize_indices(pat)
            if pat == tuple(range(self.k)):
                return shards.copy()
            with placement.batch(
                "rs_gf65536.decode_batch", True, b, nbytes=shards.nbytes
            ):
                with trace.span("ops", "pack"):
                    g = self._g_decode(pat)
                    syms = shards.view("<u2").reshape(b, k, L // 2)
                with trace.span(
                    "ops", "device", program="decode_kernel_shared"
                ):
                    out = np.asarray(
                        decode_kernel_shared(
                            jnp.asarray(g), jnp.asarray(syms)
                        )
                    )
                with trace.span("ops", "unpack"):
                    return np.ascontiguousarray(out.astype("<u2")).view(
                        np.uint8
                    ).reshape(b, k, L)
        return super().decode_batch(indices, shards)


__all__ = ["Cpu16ErasureCoder", "Xla16ErasureCoder"]
