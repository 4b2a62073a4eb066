"""Batched SHA-256 as JAX uint32 vector ops.

The RBC ECHO phase costs N^2 log N hashes per epoch network-wide
(reference docs/HONEYBADGER-EN.md:96): every node verifies a Merkle
branch for each of N shards in each of N concurrent RBC instances
(docs/RBC-EN.md:35).  Those hashes are all independent, which is
exactly what the TPU VPU wants: this module computes SHA-256 over a
*batch* axis — every op is a (B,)-wide uint32 add/rotate/xor — so one
dispatch hashes thousands of messages.

Message lengths are static per call site (shard length, 65-byte
interior nodes), so padding is baked into the traced graph and each
distinct length compiles once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5,
        0x3956C25B, 0x59F111F1, 0x923F82A4, 0xAB1C5ED5,
        0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174,
        0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
        0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7,
        0xC6E00BF3, 0xD5A79147, 0x06CA6351, 0x14292967,
        0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
        0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3,
        0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5,
        0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F, 0x682E6FF3,
        0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_H0 = np.array(
    [
        0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
        0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
    ],
    dtype=np.uint32,
)


def _rotr(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _compress_block(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """One SHA-256 compression: state (B, 8) u32, block (B, 16) u32.

    Both the 48-step message-schedule expansion and the 64 rounds run
    as fori_loops (not unrolled) so the traced graph stays small —
    compile time matters because each distinct message length is its
    own XLA program; runtime stays vectorized over the batch axis.
    """
    b = block.shape[0]
    w0 = jnp.concatenate(
        [jnp.swapaxes(block, 0, 1), jnp.zeros((48, b), dtype=jnp.uint32)]
    )  # (64, B)

    def expand(t, w):
        wm15 = w[t - 15]
        wm2 = w[t - 2]
        s0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> jnp.uint32(3))
        s1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> jnp.uint32(10))
        return w.at[t].set(w[t - 16] + s0 + w[t - 7] + s1)

    w = jax.lax.fori_loop(16, 64, expand, w0)
    k = jnp.asarray(_K)

    def round_fn(t, vs):
        a, b_, c, d, e, f, g, h = vs
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k[t] + w[t]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b_) ^ (a & c) ^ (b_ & c)
        return (t1 + s0 + maj, a, b_, c, d + t1, e, f, g)

    vs = jax.lax.fori_loop(
        0, 64, round_fn, tuple(state[:, i] for i in range(8))
    )
    return state + jnp.stack(vs, axis=1)


def _pad_to_blocks(msgs: jnp.ndarray) -> jnp.ndarray:
    """(B, L) uint8 -> (B, nblocks, 16) uint32 big-endian padded blocks."""
    b, l = msgs.shape
    nblocks = (l + 9 + 63) // 64
    padded = jnp.zeros((b, nblocks * 64), dtype=jnp.uint8)
    padded = padded.at[:, :l].set(msgs)
    padded = padded.at[:, l].set(jnp.uint8(0x80))
    bitlen = np.frombuffer(
        np.uint64(l * 8).byteswap().tobytes(), dtype=np.uint8
    )  # big-endian length, static
    padded = padded.at[:, nblocks * 64 - 8 :].set(
        jnp.asarray(bitlen, dtype=jnp.uint8)[None, :]
    )
    words = padded.reshape(b, nblocks, 16, 4).astype(jnp.uint32)
    return (
        (words[..., 0] << 24) | (words[..., 1] << 16)
        | (words[..., 2] << 8) | words[..., 3]
    )


def _digest_to_bytes(state: jnp.ndarray) -> jnp.ndarray:
    """(B, 8) u32 -> (B, 32) uint8 big-endian."""
    b = state.shape[0]
    shifts = jnp.asarray([24, 16, 8, 0], dtype=jnp.uint32)
    return (
        (state[:, :, None] >> shifts[None, None, :]) & jnp.uint32(0xFF)
    ).astype(jnp.uint8).reshape(b, 32)


@jax.jit
def sha256_batch(msgs: jnp.ndarray) -> jnp.ndarray:
    """SHA-256 of a batch of equal-length messages: (B, L) u8 -> (B, 32) u8."""
    blocks = _pad_to_blocks(msgs)
    state = jnp.broadcast_to(
        jnp.asarray(_H0), (msgs.shape[0], 8)
    ).astype(jnp.uint32)
    # scan over the (static) block count; body compiled once
    def step(st, blk):
        return _compress_block(st, blk), None
    state, _ = jax.lax.scan(step, state, jnp.swapaxes(blocks, 0, 1))
    return _digest_to_bytes(state)


@functools.cache
def _zero_digest() -> bytes:
    """Digest used to pad Merkle leaf sets to a power of two."""
    import hashlib

    return hashlib.sha256(b"cleisthenes-tpu:empty-leaf").digest()


# ---------------------------------------------------------------------------
# Device-resident Merkle kernels (consumed by ops.merkle.XlaMerkle)
# ---------------------------------------------------------------------------

_LEAF_PREFIX_BYTE = 0x00
_NODE_PREFIX_BYTE = 0x01


@jax.jit
def build_forest(shards: jnp.ndarray):
    """Build B Merkle trees in ONE XLA program.

    shards (B, n, L) uint8 -> (B, 2p-1, 32): all levels concatenated,
    leaf row first (width p = next power of two >= n), root digest
    last.  Leaf digest = SHA256(0x00 || shard), node =
    SHA256(0x01 || left || right) (ops.merkle convention).
    """
    b, n, l = shards.shape
    leaf_msgs = jnp.concatenate(
        [
            jnp.full((b * n, 1), _LEAF_PREFIX_BYTE, dtype=jnp.uint8),
            shards.reshape(b * n, l),
        ],
        axis=1,
    )
    cur = sha256_batch(leaf_msgs).reshape(b, n, 32)
    p = 1
    while p < n:
        p <<= 1
    if p != n:
        pad = jnp.broadcast_to(
            jnp.asarray(
                np.frombuffer(_zero_digest(), dtype=np.uint8)
            ),
            (b, p - n, 32),
        )
        cur = jnp.concatenate([cur, pad], axis=1)
    levels = [cur]
    width = p
    while width > 1:
        half = width // 2
        msgs = jnp.concatenate(
            [
                jnp.full((b * half, 1), _NODE_PREFIX_BYTE, dtype=jnp.uint8),
                cur.reshape(b * half, 64),
            ],
            axis=1,
        )
        cur = sha256_batch(msgs).reshape(b, half, 32)
        levels.append(cur)
        width = half
    # single (B, 2p-1, 32) output: ONE device->host transfer for the
    # whole forest instead of one per level
    return jnp.concatenate(levels, axis=1)


@jax.jit
def verify_branches(
    roots: jnp.ndarray,
    leaves: jnp.ndarray,
    branches: jnp.ndarray,
    indices: jnp.ndarray,
) -> jnp.ndarray:
    """Verify B Merkle branches in ONE XLA program.

    roots (B, 32) u8, leaves (B, L) u8 raw shard bytes, branches
    (B, D, 32) u8 sibling paths bottom-up, indices (B,) u32 -> (B,) bool.
    """
    b, l = leaves.shape
    d = branches.shape[1]
    msgs = jnp.concatenate(
        [jnp.full((b, 1), _LEAF_PREFIX_BYTE, dtype=jnp.uint8), leaves],
        axis=1,
    )
    cur = sha256_batch(msgs)
    idx = indices.astype(jnp.uint32)
    for lvl in range(d):  # d is static: unrolled into the one program
        sib = branches[:, lvl]
        bit = (idx & 1).astype(bool)[:, None]
        left = jnp.where(bit, sib, cur)
        right = jnp.where(bit, cur, sib)
        msgs = jnp.concatenate(
            [jnp.full((b, 1), _NODE_PREFIX_BYTE, dtype=jnp.uint8), left, right],
            axis=1,
        )
        cur = sha256_batch(msgs)
        idx = idx >> 1
    return (cur == roots).all(axis=1)


__all__ = ["sha256_batch", "build_forest", "verify_branches"]
