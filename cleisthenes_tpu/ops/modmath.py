"""Batched 256-bit modular arithmetic on TPU: lazy-carry Montgomery limbs.

This is the engine under both threshold-encryption share verification
and the common-coin combine (reference docs/THRESHOLD_ENCRYPTION-EN.md:
33-36, docs/BBA-EN.md:163-181; cost model "4N^2 signature sharings"
docs/HONEYBADGER-EN.md:94).  Those workloads are thousands of
*independent* modular exponentiations per epoch — perfect for a batch
axis — while each individual exponentiation is deeply sequential: 516
Montgomery products in a row for u1^e1 * u2^e2, 337 for a comb.  What
such a program costs on the chip is therefore the latency of ONE
product, not its width, and a product is written for that: limbs on
the leading axis of (22, S, 128) planes, the batch over whole vector
registers, the limb loop and the carry chain unrolled at trace time
into straight-line elementwise int32 code with no inner loop, no
dynamic slice and no row scatter.  The only loops left are the bit
ladder and the comb's three.  On a TPU the ladders and the comb's
loops run as Pallas kernels, a (22, 8, 128) block at a time out of
vector memory (2.3 us a product at 2,048 rows on a v5e chip, 9.3 at
16,384, where the same straight-line code as plain XLA takes 35 and
52: ~100 fusions a product; PERF.md section 6, PR 30); on the CPU the
same functions run as plain XLA.

Representation: a 256-bit integer is 22 limbs of 12 bits in int32.
Limb products are < 2^24 and a CIOS Montgomery pass accumulates < 22
of them plus carries per limb, staying under 2^30 — so carries can be
*lazy* (deferred to one final propagation) and everything runs in
native int32 on the VPU with zero emulation.  Montgomery radix
R = 2^264.

The default group: Z_p* with a fixed 256-bit safe prime p = 2q+1,
operating in the order-q quadratic-residue subgroup generated by g=4 —
a research-grade parameter choice, since a discrete log in a 256-bit
prime field is within reach.  A deployment that needs production
strength names RFC 3526's 2048-bit MODP-14 group instead
(``Config.crypto_group = "modp14"``, ``GROUP14``: 112-bit security),
which runs in the (11, 192) limb family on the device and through
OpenSSL (native/modpow_wide.cpp) on the host.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cleisthenes_tpu.ops import placement, widepow
from cleisthenes_tpu.ops.hashrows import be_rows_to_ints, ints_to_be_rows
from cleisthenes_tpu.parallel.mesh import host_array
from cleisthenes_tpu.utils import trace

# 256-bit safe prime p = 2q + 1 (generated deterministically, seed
# 20260729, 64-round Miller-Rabin) and the prime order q of the QR
# subgroup; g = 4 generates it.
P = 0xFFB28EF6A7CB6CEE1758160402FB047422834BD4AF8E388BD0BB1F438E9DA767
Q = (P - 1) // 2
G = 4

LIMB_BITS = 12
NLIMBS = 22  # 22 * 12 = 264 >= 256
LIMB_MASK = (1 << LIMB_BITS) - 1
R = 1 << (LIMB_BITS * NLIMBS)  # Montgomery radix 2^264
EXP_BITS = 256


def int_to_limbs(x: int) -> np.ndarray:
    out = np.zeros(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    assert x == 0, "value exceeds limb capacity"
    return out


def limbs_to_int(limbs: np.ndarray) -> int:
    x = 0
    for i in reversed(range(len(limbs))):
        x = (x << LIMB_BITS) | int(limbs[i])
    return x


def ints_to_limbs(xs: Sequence[int]) -> np.ndarray:
    """Vectorized (B,) python ints -> (B, NLIMBS) int32 limbs.

    22 limbs x 12 bits = 264 bits = 33 little-endian bytes exactly, so
    the limb split is one unpackbits + one tiny matmul instead of a
    per-int python loop (this sits on the share-verify hot path)."""
    n = len(xs)
    if n == 0:
        return np.zeros((0, NLIMBS), dtype=np.int32)
    raw = b"".join(x.to_bytes(33, "little") for x in xs)
    bits = np.unpackbits(
        np.frombuffer(raw, dtype=np.uint8).reshape(n, 33),
        axis=1,
        bitorder="little",
    )
    weights = (1 << np.arange(LIMB_BITS, dtype=np.int32))
    return bits.reshape(n, NLIMBS, LIMB_BITS).astype(np.int32) @ weights


def limbs_to_ints(arr: np.ndarray) -> List[int]:
    """Vectorized (B, NLIMBS) int32 limbs -> python ints."""
    arr = np.asarray(arr, dtype=np.int64)
    n = len(arr)
    if n == 0:
        return []
    bits = (
        (arr[:, :, None] >> np.arange(LIMB_BITS, dtype=np.int64)) & 1
    ).astype(np.uint8)
    packed = np.packbits(
        bits.reshape(n, NLIMBS * LIMB_BITS), axis=1, bitorder="little"
    )
    return [int.from_bytes(row.tobytes(), "little") for row in packed]


def exps_to_bits(exps: Sequence[int]) -> np.ndarray:
    """(B,) ints -> (B, 256) int32 bits, MSB first."""
    bits = np.unpackbits(exps_to_bytes(exps))
    return bits.reshape(len(exps), EXP_BITS).astype(np.int32)


def exps_to_bytes(exps: Sequence[int]) -> np.ndarray:
    """(B,) ints -> (B, 32) uint8 big-endian — the packed exponent
    form shipped to the device (32x smaller than the bit plane)."""
    return np.frombuffer(
        b"".join(e.to_bytes(32, "big") for e in exps), dtype=np.uint8
    ).reshape(len(exps), 32)


def ints_to_bytes33(xs: Sequence[int]) -> np.ndarray:
    """(B,) ints -> (B, 33) uint8 little-endian (264-bit capacity)."""
    return np.frombuffer(
        b"".join(x.to_bytes(33, "little") for x in xs), dtype=np.uint8
    ).reshape(len(xs), 33)


def bytes33_to_ints(arr: np.ndarray) -> List[int]:
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    return [int.from_bytes(row.tobytes(), "little") for row in arr]


# ---------------------------------------------------------------------------
# Group parameters: the modulus seam
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GroupParams:
    """One multiplicative group: safe prime p = 2q + 1 and a generator
    g of the order-q QR subgroup.  The engine, TPKE and the coin are
    parametrized by this, so swapping primes touches no callers — the
    seam ops/tpke.py's security notes promise, demonstrated by
    tests/test_groups.py.  Hashable: engines cache per group."""

    p: int
    q: int
    g: int

    @property
    def nbytes(self) -> int:
        """Byte width of a group element (transcript hashing width)."""
        return (self.p.bit_length() + 7) // 8


DEFAULT_GROUP = GroupParams(p=P, q=Q, g=G)


@functools.cache
def _spec256(group: GroupParams) -> tuple:
    """Montgomery constants for a <=256-bit group in the fixed 22x12
    limb layout: (m_limbs, m_prime, r_limbs, r2_limbs).  The layout
    (NLIMBS/LIMB_BITS, 33-byte packed I/O) is static, so ONE compiled
    XLA kernel serves every such group — the constants ride in as
    ordinary traced arrays.  Larger groups use the cpu engine."""
    p = group.p
    if p.bit_length() > 256 or p % 2 == 0:
        raise ValueError(
            f"group modulus ({p.bit_length()} bits) exceeds the 256-bit "
            "XLA limb layout; use the cpu engine for larger groups"
        )
    return (
        int_to_limbs(p),
        (-pow(p, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS),
        int_to_limbs(R % p),
        int_to_limbs((R * R) % p),
    )


# Precomputed scalars for the default p (mont_mul_batch's surface)
_P_LIMBS, _M_PRIME, *_ = _spec256(DEFAULT_GROUP)


class LimbLayout(NamedTuple):
    """One XLA limb layout family: values are (nlimbs, S, 128) int32
    limb planes of limb_bits each; packed I/O is nlimbs*limb_bits/8 bytes
    per value (the product is always byte-aligned by construction).
    Each family compiles its own kernels once; the group constants
    stay traced, so one family serves every group that fits it."""

    limb_bits: int
    nlimbs: int

    @property
    def bits(self) -> int:
        return self.limb_bits * self.nlimbs

    @property
    def val_bytes(self) -> int:
        return self.bits // 8


# Widths beyond the tuned 256-bit layout.  Constraints per family:
# nlimbs * limb_bits divisible by 8 (byte-aligned packing) and
# 2 * nlimbs * (2^limb_bits - 1)^2 < 2^31 (lazy-carry int32 bound —
# see _make_mont_mul).  The 2112-bit family hosts the 2048-bit MODP-14
# group; the 384-bit family is sized for a BLS12-381-class base field.
_WIDE_LAYOUTS = (
    LimbLayout(12, 32),   # <=  384 bits,  48-byte values
    LimbLayout(11, 72),   # <=  792 bits,  99-byte values
    LimbLayout(11, 192),  # <= 2112 bits, 264-byte values
)
LAYOUT256 = LimbLayout(LIMB_BITS, NLIMBS)
# The families whose ladders run as Pallas kernels on a TPU: the
# 256-bit one and MODP-14's 2112-bit one.  (12,32) and (11,72) stay
# plain XLA, the form their WIDE_FLOORS were carried over against.
_KERNEL_LAYOUTS = (LAYOUT256, LimbLayout(11, 192))


def layout_for_group(group: GroupParams) -> Optional[LimbLayout]:
    """The smallest XLA limb family that fits ``group`` (None when no
    family does, or the modulus is even — callers fall back to the cpu
    engine)."""
    bits = group.p.bit_length()
    if group.p % 2 == 0:
        return None
    if bits <= LAYOUT256.bits:
        return LAYOUT256
    for lay in _WIDE_LAYOUTS:
        if bits <= lay.bits:
            assert 2 * lay.nlimbs * ((1 << lay.limb_bits) - 1) ** 2 < (
                1 << 31
            )
            return lay
    return None


def xla_capable(group: GroupParams) -> bool:
    """True when some XLA limb family hosts this group — i.e. the
    'tpu' engine accepts it.  Callers that degrade to 'cpu' for
    unsupported groups gate on THIS, not on a width constant."""
    return layout_for_group(group) is not None


def get_engine_degraded(
    backend: str, mesh=None, group: GroupParams = DEFAULT_GROUP
) -> "ModEngine":
    """``get_engine`` that silently degrades 'tpu' to 'cpu' when no
    XLA limb family hosts ``group`` — the ONE home of that decision
    for every caller whose contract is "accelerate when possible"
    (ops.tpke, ops.dkg).  Plain ``get_engine`` still raises, for
    callers that explicitly demanded the device."""
    if backend == "tpu" and not xla_capable(group):
        backend = "cpu"
    return get_engine(backend, mesh, group)


# A 384-bit safe prime group (p = 2q+1, g = 4 generates the order-q QR
# subgroup) — the width class of a BLS12-381 base field, hosted by the
# (12, 32) XLA family.  Research vehicle for the wide path (the same
# caveat as DEFAULT_GROUP's 256-bit security note applies).
P384 = int(
    "F7E12F10702F5E910CBEC741E84E2608D29D655C81BF7BF093B38ED4267537C9"
    "249C8FE3A20A0C68153E6DAA5F9A23F3",
    16,
)
GROUP384 = GroupParams(p=P384, q=(P384 - 1) // 2, g=4)

# RFC 3526 section 3: the 2048-bit MODP group (id 14), the smallest
# finite-field group NIST SP 800-57 Part 1 (Table 2) rates at 112-bit
# security.  p = 2q + 1 is a safe prime; the RFC's generator g = 2 is
# a quadratic residue, since p = 7 (mod 8), so it generates the order-q
# subgroup the threshold schemes work in.  The (11, 192) limb family
# hosts it on the device, native/modpow_wide.cpp on the host.
MODP14 = int(
    "FFFFFFFFFFFFFFFFC90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74"
    "020BBEA63B139B22514A08798E3404DDEF9519B3CD3A431B302B0A6DF25F1437"
    "4FE1356D6D51C245E485B576625E7EC6F44C42E9A637ED6B0BFF5CB6F406B7ED"
    "EE386BFB5A899FA5AE9F24117C4B1FE649286651ECE45B3DC2007CB8A163BF05"
    "98DA48361C55D39A69163FA8FD24CF5F83655D23DCA3AD961C62F356208552BB"
    "9ED529077096966D670C354E4ABC9804F1746C08CA18217C32905E462E36CE3B"
    "E39E772C180E86039B2783A2EC07A28FB5C55DF06F4C52C9DE2BCBF695581718"
    "3995497CEA956AE515D2261898FA051015728E5A8AACAA68FFFFFFFFFFFFFFFF",
    16,
)
GROUP14 = GroupParams(p=MODP14, q=(MODP14 - 1) // 2, g=2)

# The groups a deployment names by Config.crypto_group
GROUPS = {"default": DEFAULT_GROUP, "modp14": GROUP14}


def group_named(name: str) -> GroupParams:
    """The group ``Config.crypto_group`` names."""
    try:
        return GROUPS[name]
    except KeyError:
        raise ValueError(
            f"unknown crypto_group {name!r}; one of {tuple(GROUPS)}"
        ) from None


def _int_to_limbs_w(x: int, lay: LimbLayout) -> np.ndarray:
    out = np.zeros(lay.nlimbs, dtype=np.int32)
    mask = (1 << lay.limb_bits) - 1
    for i in range(lay.nlimbs):
        out[i] = x & mask
        x >>= lay.limb_bits
    return out


@functools.cache
def _spec_wide(group: GroupParams, lay: LimbLayout) -> tuple:
    """Montgomery constants for ``group`` in the ``lay`` limb layout
    (same contract as _spec256, radix R = 2^lay.bits)."""
    p = group.p
    if p.bit_length() > lay.bits or p % 2 == 0:
        raise ValueError(
            f"group modulus ({p.bit_length()} bits) does not fit the "
            f"{lay.bits}-bit layout"
        )
    r = 1 << lay.bits
    return (
        _int_to_limbs_w(p, lay),
        (-pow(p, -1, 1 << lay.limb_bits)) % (1 << lay.limb_bits),
        _int_to_limbs_w(r % p, lay),
        _int_to_limbs_w((r * r) % p, lay),
    )


def _ints_to_val_bytes(xs: Sequence[int], lay: LimbLayout) -> np.ndarray:
    """(B,) ints -> (B, val_bytes) uint8 little-endian."""
    vb = lay.val_bytes
    return np.frombuffer(
        b"".join(x.to_bytes(vb, "little") for x in xs), dtype=np.uint8
    ).reshape(len(xs), vb)


# bytes33_to_ints is already width-generic (per-row little-endian
# from_bytes) — the wide path reuses it under a layout-neutral name
_val_bytes_to_ints = bytes33_to_ints


def _exps_to_bytes_w(exps: Sequence[int], lay: LimbLayout) -> np.ndarray:
    """(B,) ints -> (B, val_bytes) uint8 big-endian exponents."""
    vb = lay.val_bytes
    return np.frombuffer(
        b"".join(e.to_bytes(vb, "big") for e in exps), dtype=np.uint8
    ).reshape(len(exps), vb)


# ---------------------------------------------------------------------------
# The Montgomery product: straight-line code over limb planes
# ---------------------------------------------------------------------------
#
# LAYOUT: a batch of values is (nlimbs, S, 128) int32: limbs on the
# leading, untiled axis, the batch over whole (sublane, lane) tiles
# (padded to a multiple of 128 inside the program).  Every limb is a
# plane of full vector registers, every per-limb step of the product
# is one elementwise operation on whole registers, and the CIOS shift
# re-indexes planes without moving a byte.  The product itself is
# rank-agnostic in what follows the limb axis: the comb builds its
# tables on (nlimbs, 1, n_bases) and accumulates on
# (nlimbs, G / rep, n_bases * rep).

_LANES = 128
# Sublanes a kernel block (TPU): one vector register a limb plane.
_TILE_ROWS = 8
# Layouts of at most this many limbs get the product unrolled at
# trace time (22 limbs: ~2,200 operations).  The wide families keep
# the limb loop: unrolled, the 72- and 192-limb products would be
# 20-150 thousand operations, and the 32-limb one, ~4,400, took the
# CPU 36-41 s to compile in each of tests/test_groups.py's 384-bit
# tests where the loop takes 2.
_STRAIGHT_LINE_MAX_LIMBS = 24


def _make_mont_mul(limb_bits: int, nlimbs: int, kernel: bool = False):
    """Build the lazy-carry CIOS Montgomery product for one limb
    layout.  The 256-bit layout and every wide layout share THIS one
    builder; the form (straight-line or looped over limbs) follows
    from ``nlimbs`` at trace time.  ``kernel``: the product of a
    ladder that runs as a Pallas kernel on a TPU (_KERNEL_LAYOUTS),
    which there takes the loop's kernel form.

    ``mont_mul(a, b, m_col, m_prime)``: a, b (nlimbs, ...) int32 with
    canonical limbs (< 2^limb_bits); m_col the modulus limbs,
    broadcastable against them ((nlimbs, 1, 1) or whole planes);
    m_prime = -p^-1 mod 2^limb_bits.  Returns a*b*R^-1 mod p as
    canonical limbs in [0, p).

    Lazy-carry overflow bound: each limb step adds two limb products
    of < 2^(2*limb_bits) per limb, so t stays below
    2 * nlimbs * 2^(2*limb_bits), which must fit int32 — every entry
    of _WIDE_LAYOUTS satisfies it (checked in layout_for_group)."""
    mask = (1 << limb_bits) - 1

    def limb_step(t, ai, b, m_col, m_prime):
        t = t + ai * b
        m = ((t[0] & mask) * m_prime) & mask
        t = t + m * m_col
        # divide by the radix: limb 0 is now = 0 mod 2^limb_bits; drop
        # it and fold its high bits into the limb above
        return jnp.concatenate(
            [t[1:2] + (t[0:1] >> limb_bits), t[2:], jnp.zeros_like(t[:1])],
            axis=0,
        )

    def canonical(t, m_col):
        # one carry propagation -> canonical limbs, of t and of t - p
        # side by side (the result is < 2p, so one of the two is it):
        # chains over planes, stacked once.  A limb of t - p may be
        # negative; the arithmetic shift carries its borrow.
        kept, less, carry, borrow = [], [], 0, 0
        for j in range(nlimbs):
            v = t[j] + carry
            d = t[j] - m_col[j] + borrow
            # the top limb keeps its overflow (t may pass the radix
            # where p fills the layout, t - p never does) and its sign
            top = j == nlimbs - 1
            kept.append(v if top else v & mask)
            less.append(d if top else d & mask)
            carry, borrow = v >> limb_bits, d >> limb_bits
        return jnp.where(
            (less[-1] < 0)[None], jnp.stack(kept), jnp.stack(less)
        )

    def straight_line(a, b, m_col, m_prime):
        t = jnp.zeros_like(a)
        for i in range(nlimbs):
            t = limb_step(t, a[i], b, m_col, m_prime)
        return canonical(t, m_col)

    def carried(t, m_col):
        # ``canonical`` as loops over the limbs, with dynamic updates
        def carry_pass(j, t):
            c = t[j] >> limb_bits
            return t.at[j].set(t[j] & mask).at[j + 1].add(c)

        t = jax.lax.fori_loop(0, nlimbs - 1, carry_pass, t)
        m_rows = jnp.broadcast_to(m_col, t.shape)

        def borrow_pass(j, state):
            d, borrow = state
            diff = t[j] - m_rows[j] - borrow
            new_borrow = (diff >> 31) & 1
            return d.at[j].set(diff + (new_borrow << limb_bits)), new_borrow

        d, borrow = jax.lax.fori_loop(
            0, nlimbs, borrow_pass, (jnp.zeros_like(t), jnp.zeros_like(t[0]))
        )
        return jnp.where((borrow == 0)[None], d, t)

    def looped(a, b, m_col, m_prime):
        def outer(i, t):
            ai = jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False)
            return limb_step(t, ai, b, m_col, m_prime)

        t = jax.lax.fori_loop(0, nlimbs, outer, jnp.zeros_like(a), unroll=2)
        return carried(t, m_col)

    def rotated(a, b, m_col, m_prime):
        # ``looped`` as a kernel body has it: a's limbs rotate through
        # limb 0, since a kernel has no dynamic slice of a value, and
        # the carry is unrolled (a few operations a limb, against the
        # loop's few hundred; in plain XLA, over 192 limbs, it took the
        # CPU ~30 s to compile)
        def outer(_, state):
            t, a = state
            t = limb_step(t, a[0], b, m_col, m_prime)
            return t, jnp.concatenate([a[1:], a[:1]], axis=0)

        t, _ = jax.lax.fori_loop(0, nlimbs, outer, (jnp.zeros_like(a), a))
        return canonical(t, m_col)

    def in_kernel(a, b, m_col, m_prime):
        # _run_planes makes the same choice at the same trace
        return (rotated if _on_tpu() else looped)(a, b, m_col, m_prime)

    if nlimbs <= _STRAIGHT_LINE_MAX_LIMBS:
        form = straight_line
    else:
        form = in_kernel if kernel else looped
    # jitted: a program traces the product once a shape, however many
    # times its text holds it (the kernels all share one block shape)
    return jax.jit(form)


_mont_mul = _make_mont_mul(LIMB_BITS, NLIMBS)


def _to_planes(x: jnp.ndarray) -> jnp.ndarray:
    """(k, B) -> (k, S, 128): the batch over whole lane rows, padded
    with zeros to a multiple of 128; a batch of under one row stays
    (k, 1, B), as short as it is."""
    if x.shape[1] < _LANES:
        return x[:, None, :]
    pad = -x.shape[1] % _LANES
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(x.shape[0], -1, _LANES)


def _from_planes(x: jnp.ndarray, b: int) -> jnp.ndarray:
    """(k, S, 128) -> (k, b): ``_to_planes`` undone."""
    return x.reshape(x.shape[0], -1)[:, :b]


@jax.jit
def mont_mul_batch(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Default-group Montgomery product on (B, NLIMBS) canonical limbs
    (property-test surface; the limb-plane product inside)."""
    out = _mont_mul(
        _to_planes(a.T),
        _to_planes(b.T),
        jnp.asarray(_P_LIMBS)[:, None, None],
        _M_PRIME,
    )
    return _from_planes(out, a.shape[0]).T


# -- packed device I/O: values cross host<->device as raw bytes
# (33 B/value, 32 B/exponent) and are unpacked/repacked on device,
# bytes and limbs both on the leading axis.


def _bytes33_to_limbs_dev(b: jnp.ndarray) -> jnp.ndarray:
    """(33, ...) little-endian bytes -> (NLIMBS, ...) int32 limbs:
    three bytes are two 12-bit limbs."""
    x = b.astype(jnp.int32).reshape((11, 3) + b.shape[1:])
    lo = x[:, 0] | ((x[:, 1] & 0xF) << 8)
    hi = (x[:, 1] >> 4) | (x[:, 2] << 4)
    return jnp.stack([lo, hi], axis=1).reshape((NLIMBS,) + b.shape[1:])


def _limbs_to_bytes33_dev(limbs: jnp.ndarray) -> jnp.ndarray:
    """(NLIMBS, ...) int32 canonical limbs -> (33, ...) uint8
    little-endian bytes."""
    x = limbs.reshape((11, 2) + limbs.shape[1:])
    lo, hi = x[:, 0], x[:, 1]
    out = jnp.stack(
        [lo & 0xFF, (lo >> 8) | ((hi & 0xF) << 4), hi >> 4], axis=1
    )
    return out.reshape((33,) + limbs.shape[1:]).astype(jnp.uint8)


def _bits_to_limbs_dev(b: jnp.ndarray, lay: LimbLayout) -> jnp.ndarray:
    """``_bytes33_to_limbs_dev`` for any layout, bit by bit:
    (val_bytes, ...) little-endian bytes -> (nlimbs, ...) limbs."""
    bits = (
        b.astype(jnp.int32)[:, None] >> _iota(8, b.ndim)
    ) & 1  # (val_bytes, 8, ...)
    bits = bits.reshape((lay.nlimbs, lay.limb_bits) + b.shape[1:])
    return (bits << _iota(lay.limb_bits, b.ndim)).sum(axis=1)


def _limbs_to_bits_dev(limbs: jnp.ndarray, lay: LimbLayout) -> jnp.ndarray:
    """(nlimbs, ...) canonical limbs -> (val_bytes, ...) uint8
    little-endian bytes, any layout."""
    bits = (limbs[:, None] >> _iota(lay.limb_bits, limbs.ndim)) & 1
    bits = bits.reshape((lay.val_bytes, 8) + limbs.shape[1:])
    return (bits << _iota(8, limbs.ndim)).sum(axis=1).astype(jnp.uint8)


def _iota(n: int, ndim: int) -> jnp.ndarray:
    """0..n-1 along axis 1 of an (x, n, ...) array of ndim + 1 axes."""
    return jnp.arange(n, dtype=jnp.int32).reshape((1, n) + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# The bit ladders
# ---------------------------------------------------------------------------
#
# A ladder is a pure function of limb planes, elementwise over
# whatever follows the leading axis.  ``consts`` carries the group:
# rows [0, nl) the modulus limbs, [nl, 2nl) R mod p (Montgomery one),
# [2nl, 3nl) R^2 mod p, row 3nl m_prime; (3nl + 1, 1, 1), or whole
# planes inside a kernel block.  An exponent is (words, ...) int32,
# word 0 the most significant; a step reads its top bit and shifts
# the whole exponent left by one: no index that depends on the step.
# A step's multiplier is picked at the end of the step before and
# carried: a loop-carried array is materialised once, where a select
# left beside its product is fused into, and recomputed by, every
# limb step of it (XLA on the CPU: 5x the program's time).


def _exp_words(e: jnp.ndarray, word_bytes: int) -> jnp.ndarray:
    """(exp_bytes, ...) big-endian bytes -> (exp_bytes / word_bytes,
    ...) int32 words of ``word_bytes`` bytes, most significant first."""
    x = e.astype(jnp.int32).reshape(
        (e.shape[0] // word_bytes, word_bytes) + e.shape[1:]
    )
    out = x[:, 0]
    for k in range(1, word_bytes):
        out = (out << 8) | x[:, k]
    return out


def _group_of(mul_raw, nl: int, consts):
    """(product, Montgomery one, R^2) of a group from its column or
    planes ``consts``."""
    m_col, one_m, r2 = (consts[k * nl : (k + 1) * nl] for k in range(3))

    def mul(a, b):
        return mul_raw(a, b, m_col, consts[3 * nl])

    return mul, one_m, r2


def _from_montgomery(mul, acc):
    one = jnp.concatenate(
        [jnp.ones_like(acc[:1]), jnp.zeros_like(acc[1:])], axis=0
    )
    return mul(acc, one)


def _make_ladders(lay: LimbLayout, kernel: bool = False):
    """(pow ladder, dual-pow ladder, bytes of an exponent word) of one
    limb layout; ``kernel`` as ``_make_mont_mul`` takes it."""
    nl = lay.nlimbs
    mul_raw = _make_mont_mul(lay.limb_bits, nl, kernel)
    # exponents cross the seam as 32 bytes in the 256-bit layout, as
    # val_bytes in a wide one
    exp_bytes = EXP_BITS // 8 if lay == LAYOUT256 else lay.val_bytes
    word_bytes = 4 if exp_bytes % 4 == 0 else 1
    top = 8 * word_bytes - 1
    word_mask = -1 if word_bytes == 4 else (1 << (top + 1)) - 1

    def top_bit(e):
        return ((e[0] >> top) & 1) == 1

    def shift_left(e):
        below = jnp.concatenate([e[1:], jnp.zeros_like(e[:1])], axis=0)
        return ((e << 1) & word_mask) | ((below >> top) & 1)

    def pow_ladder(consts, base, e):
        """base^e, square-and-multiply from the top bit."""
        mul, one_m, r2 = _group_of(mul_raw, nl, consts)
        one_m = jnp.broadcast_to(one_m, base.shape)
        base_m = mul(base, jnp.broadcast_to(r2, base.shape))

        def multiplier(e):
            return jnp.where(top_bit(e)[None], base_m, one_m)

        def step(_, state):
            acc, mult, e = state
            acc = mul(mul(acc, acc), mult)
            e = shift_left(e)
            return acc, multiplier(e), e

        acc, _, _ = jax.lax.fori_loop(
            0, 8 * exp_bytes, step, (one_m, multiplier(e), e)
        )
        return _from_montgomery(mul, acc)

    def dual_ladder(consts, u1, u2, e1, e2):
        """u1^e1 * u2^e2 (Shamir's trick: one square and one
        select-multiply a bit, the Chaum-Pedersen verification
        shape)."""
        mul, one_m, r2 = _group_of(mul_raw, nl, consts)
        one_m = jnp.broadcast_to(one_m, u1.shape)
        r2 = jnp.broadcast_to(r2, u1.shape)
        u1_m = mul(u1, r2)
        u2_m = mul(u2, r2)
        u12_m = mul(u1_m, u2_m)

        def multiplier(e1, e2):
            b1, b2 = top_bit(e1)[None], top_bit(e2)[None]
            return jnp.where(
                b1 & b2,
                u12_m,
                jnp.where(b1, u1_m, jnp.where(b2, u2_m, one_m)),
            )

        def step(_, state):
            acc, mult, e1, e2 = state
            acc = mul(mul(acc, acc), mult)
            e1, e2 = shift_left(e1), shift_left(e2)
            return acc, multiplier(e1, e2), e1, e2

        acc, _, _, _ = jax.lax.fori_loop(
            0, 8 * exp_bytes, step, (one_m, multiplier(e1, e2), e1, e2)
        )
        return _from_montgomery(mul, acc)

    return pow_ladder, dual_ladder, word_bytes


def _group_consts(m_limbs, m_prime, r_limbs, r2_limbs) -> jnp.ndarray:
    """The traced group constants as one (3 * nlimbs + 1, 1, 1)
    column (see the ladders for the rows)."""
    return jnp.concatenate(
        [m_limbs, r_limbs, r2_limbs, jnp.reshape(m_prime, (1,))]
    ).astype(jnp.int32)[:, None, None]


# ---------------------------------------------------------------------------
# Where a program runs: Pallas kernels on a TPU, the same functions as
# plain XLA elsewhere
# ---------------------------------------------------------------------------
#
# Read on a v5e chip (PERF.md section 6, PR 30): the straight-line
# product as plain XLA is still ~100 fusions a product (XLA will not
# fuse across the limb-0 dependency of the CIOS chain, nor along a
# carry chain): 35 us a product at 2,048 rows, 52 at 16,384, where the
# looped form it replaced took 43 and 113.  The same functions as the
# body of a kernel, a (limbs, 8, 128) block at a time out of vector
# memory, take 2.3 and 9.3.  So on a TPU every product of the 256-bit
# programs runs inside a kernel; on the CPU (the tests, the dry runs)
# the same functions run as they are.  The process's backend picks at
# trace time, nothing a caller sets (and not
# ``jax.lax.platform_dependent``, which traces both forms of every
# program: seconds of a warm start).


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile_call(
    body, out_leads, consts, planes, resident=(), **compiler_params
):
    """``body(consts_ref, *resident_refs, *plane_refs, *out_refs)`` as
    a Pallas kernel over the (_TILE_ROWS, 128) tiles of the planes'
    two trailing axes (padded to whole tiles here, cut back on
    return).  ``out_leads``: the leading axes of each output.
    ``resident``: arrays whose last axis is the planes' lane axis,
    blocked by lane tile alone; the lane tile is the outer grid axis,
    so such a block is fetched once and serves all its tile's rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, lanes = planes[0].shape[-2:]
    pad = (-rows % _TILE_ROWS, -lanes % _LANES)
    if any(pad):
        planes = tuple(
            jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pad[0]), (0, pad[1])))
            for x in planes
        )
        resident = tuple(
            jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad[1]),))
            for x in resident
        )
    tiled = (rows + pad[0], lanes + pad[1])
    consts = jnp.broadcast_to(consts, (consts.shape[0], _TILE_ROWS, _LANES))

    def block(lead):
        n = len(lead)
        return pl.BlockSpec(
            tuple(lead) + (_TILE_ROWS, _LANES),
            lambda j, i: (0,) * n + (i, j),
        )

    def lane_block(x):
        n = x.ndim - 1
        return pl.BlockSpec(
            x.shape[:-1] + (_LANES,), lambda j, i: (0,) * n + (j,)
        )

    outs = pl.pallas_call(
        body,
        grid=(tiled[1] // _LANES, tiled[0] // _TILE_ROWS),
        in_specs=[pl.BlockSpec(consts.shape, lambda j, i: (0, 0, 0))]
        + [lane_block(x) for x in resident]
        + [block(x.shape[:-2]) for x in planes],
        out_specs=[block(lead) for lead in out_leads],
        out_shape=[
            jax.ShapeDtypeStruct(tuple(lead) + tiled, jnp.int32)
            for lead in out_leads
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), **compiler_params
        ),
    )(consts, *resident, *planes)
    return [out[..., :rows, :lanes] for out in outs]


def _run_planes(fn, consts, *planes):
    """``fn(consts, *planes)``, a pure function of limb planes (a whole
    ladder): on a TPU one kernel a call, a block at a time out of
    vector memory."""

    def kernel(consts, *planes):
        def body(consts_ref, *refs):
            refs[-1][...] = fn(consts_ref[...], *(r[...] for r in refs[:-1]))

        return _tile_call(body, [planes[0].shape[:1]], consts, planes)[0]

    return (kernel if _on_tpu() else fn)(consts, *planes)


def _iterate(step, length, every, consts, state, *planes):
    """The states after steps ``every``, ``2 * every``, ... of
    ``length`` applications of ``state = step(consts, state,
    *planes)``, stacked on a new leading axis: a scan of one step an
    iteration as plain XLA; on a TPU one kernel that stores a state
    every ``every`` steps."""

    def scanned(consts, state, *planes):
        def one(state, _):
            state = step(consts, state, *planes)
            return state, state

        _, states = jax.lax.scan(one, state, None, length=length)
        return states[every - 1 :: every]

    def kernel(consts, state, *planes):
        def body(consts_ref, state_ref, *refs):
            consts = consts_ref[...]
            held = tuple(r[...] for r in refs[:-1])

            def some(i, state):
                for _ in range(every):
                    state = step(consts, state, *held)
                refs[-1][i] = state
                return state

            jax.lax.fori_loop(0, length // every, some, state_ref[...])

        lead = (length // every,) + state.shape[:1]
        return _tile_call(body, [lead], consts, (state,) + planes)[0]

    return (kernel if _on_tpu() else scanned)(consts, state, *planes)


def _jit_program(program, batched: int, name: str):
    """``program(*batched arrays, *group constants)`` jitted under
    ``name`` (the profiler's module is ``jit_<name>``), with the jax
    Mesh the batch is sharded over as the static keyword ``mesh``
    (None: one device).  Under a mesh each device runs ``program`` on
    its own rows (``shard_map``: a kernel is not GSPMD's to partition,
    and a program that never looks across rows needs no collective)."""

    def fused(*args, mesh=None):
        if mesh is None:
            return program(*args)
        from jax.sharding import PartitionSpec

        rows = PartitionSpec(mesh.axis_names)
        return jax.shard_map(
            program,
            mesh=mesh,
            in_specs=(rows,) * batched + (PartitionSpec(),) * 4,
            out_specs=rows,
            check_vma=False,
        )(*args)

    fused.__name__ = name
    return jax.jit(fused, static_argnames="mesh")


def _make_programs(lay: LimbLayout):
    """The jitted packed-I/O pow and dual-pow programs of one limb
    layout: unpack, to-Montgomery, exponentiate, from-Montgomery,
    repack in ONE program (one dispatch, one transfer each way).  The
    group constants are traced arguments, so one compiled program
    serves every group the layout fits.  The ladders of
    _KERNEL_LAYOUTS run as Pallas kernels on a TPU; the other wide
    families' stay plain XLA everywhere."""
    kernel = lay in _KERNEL_LAYOUTS
    pow_ladder, dual_ladder, word_bytes = _make_ladders(lay, kernel)
    is256 = lay == LAYOUT256

    def values(b):  # (B, val_bytes) uint8 LE -> limb planes
        x = _to_planes(b.T)
        if is256:
            return _bytes33_to_limbs_dev(x)
        return _bits_to_limbs_dev(x, lay)

    def exponents(e):  # (B, exp bytes) uint8 BE -> word planes
        return _exp_words(_to_planes(e.T), word_bytes)

    def packed(limbs, b):  # limb planes -> (b, val_bytes) uint8 LE
        if is256:
            out = _limbs_to_bytes33_dev(limbs)
        else:
            out = _limbs_to_bits_dev(limbs, lay)
        return _from_planes(out, b).T

    def run(ladder, consts, planes):
        if kernel:
            return _run_planes(ladder, consts, *planes)
        return ladder(consts, *planes)

    def pow_program(base_b, exp_b, *group):
        planes = (values(base_b), exponents(exp_b))
        out = run(pow_ladder, _group_consts(*group), planes)
        return packed(out, len(base_b))

    def dual_pow_program(u1_b, e1_b, u2_b, e2_b, *group):
        planes = (
            values(u1_b), values(u2_b), exponents(e1_b), exponents(e2_b)
        )
        out = run(dual_ladder, _group_consts(*group), planes)
        return packed(out, len(u1_b))

    # the profiler's modules (jit_<name>) are read by these names: the
    # 256-bit programs' by benchmarks/layer_metrics/modexp256_roofline.py,
    # which counts no wide program
    suffix = "fused" if is256 else "wide"
    return (
        _jit_program(pow_program, 2, "_pow_" + suffix),
        _jit_program(dual_pow_program, 4, "_dual_pow_" + suffix),
    )


# Packed-bytes in/out, single-dispatch base^e and u1^e1 * u2^e2 of the
# 256-bit layout: (B, 33) uint8 LE values, (B, 32) uint8 BE exponents,
# the _spec256 constants, and the jax Mesh the batch is sharded over
# (None on one device).
_pow_fused, _dual_pow_fused = _make_programs(LAYOUT256)


def _comb_group(consts):
    return _group_of(_mont_mul, NLIMBS, consts)


def _comb_to_montgomery(consts, x):
    mul, _one_m, r2 = _comb_group(consts)
    return mul(x, jnp.broadcast_to(r2, x.shape))


def _comb_square(consts, s):
    mul = _comb_group(consts)[0]
    return mul(s, s)


def _comb_times(consts, t, s):
    return _comb_group(consts)[0](t, s)


def _comb_select(entries, nib):
    """Of an item's 16 table entries the one its nibble names:
    integer selects by compares, no contraction.  ``entries[j]``
    broadcasts against ``nib`` along the sublanes."""
    sel = entries[0]
    for j in range(1, 16):
        sel = jnp.where(nib == j, entries[j], sel)
    return sel


def _comb_accumulate(consts, table, nibs):
    """The 64 comb steps: acc *= T[k, nib[k]], then out of the
    Montgomery domain.  ``table`` (64, 16, NLIMBS, L), ``nibs``
    (64, Gs, L) int32; returns canonical limbs (NLIMBS, Gs, L)."""

    def scanned(consts, table, nibs):
        mul, one_m, _r2 = _comb_group(consts)

        def step(state, ahead):
            # the entries are selected a step ahead and carried (see
            # the ladders' note on the multiplier)
            acc, sel = state
            t_k, nib_k = ahead
            return (mul(acc, sel), _comb_select(t_k[:, :, None], nib_k)), None

        acc0 = jnp.broadcast_to(one_m, (NLIMBS,) + nibs.shape[1:])
        (acc, _), _ = jax.lax.scan(
            step,
            (acc0, _comb_select(table[0][:, :, None], nibs[0])),
            (jnp.roll(table, -1, axis=0), jnp.roll(nibs, -1, axis=0)),
        )
        return _from_montgomery(mul, acc)

    def kernel(consts, table, nibs):
        def body(consts_ref, table_ref, nibs_ref, out_ref):
            mul, one_m, _r2 = _comb_group(consts_ref[...])

            def step(k, acc):
                nib = nibs_ref[k]
                hot = [nib == j for j in range(1, 16)]
                planes = []
                for limb in range(NLIMBS):
                    # a (1, 128) row of the table, read along the
                    # sublanes as it lies
                    sel = table_ref[k, 0, limb : limb + 1, :]
                    for j in range(1, 16):
                        sel = jnp.where(
                            hot[j - 1],
                            table_ref[k, j, limb : limb + 1, :],
                            sel,
                        )
                    planes.append(sel)
                return mul(acc, jnp.stack(planes))

            acc = jax.lax.fori_loop(0, 64, step, one_m)
            out_ref[...] = _from_montgomery(mul, acc)

        # a lane tile's table is 12.6 MB of vector memory, twice with
        # its double buffer
        return _tile_call(
            body, [(NLIMBS,)], consts, (nibs,), resident=(table,),
            vmem_limit_bytes=48 << 20,
        )[0]

    return (kernel if _on_tpu() else scanned)(consts, table, nibs)


def _comb_program(bases_b, exps_b, *group):
    """Fixed-base comb exponentiation for GROUPED batches: every item
    of group i is a power of bases[i].

    The protocol's exponentiation mass has exactly this shape — a
    wave issues N shares per instance against one shared base (the
    coin's hashed base, a ciphertext's c1, or the group generator g;
    ops.tpke.issue_share_columns for the lockstep executor's byte
    columns, issue_shares_batch for int lists) — and a shared base
    makes the classic comb trade: build the 64x16 table of
    base^(j * 16^k) ONCE per base on device (271 Montgomery products
    in a row at the width of n_bases, shared by the whole group), then
    each exponentiation is 64 table-select multiplies instead of the
    generic ladder's 512 square-and-multiply steps.  Three loops: the
    squarings that give base^(16^k), the 15 table rows, the 64 comb
    steps (scans as plain XLA, a kernel each on a TPU).  Returns
    (n_bases, G, 33) uint8 LE.

    Layouts: the tables build on (NLIMBS, 1, n_bases) and
    (NLIMBS, 64, n_bases), bases on the lanes; the accumulator is
    (NLIMBS, G / rep, n_bases * rep): an item's lane is its base's
    lane, so a table row is read along the sublanes as it lies, and
    ``rep`` items of a base sit side by side so that few bases still
    fill the 128 lanes.  Under a mesh the base rows shard flat and
    each device runs the whole comb on its own: a table builds where
    its exponents are.
    """
    n_bases, G = exps_b.shape[0], exps_b.shape[1]
    consts = _group_consts(*group)

    # ---- the table: T[k, j] = base^(j * 16^k), Montgomery domain,
    # bases on the lanes ----
    base = _bytes33_to_limbs_dev(bases_b.T[:, None, :])
    base_m = _run_planes(_comb_to_montgomery, consts, base)
    # base^(16^k): every fourth of 252 squarings in a row
    powers = jnp.concatenate(
        [base_m[None], _iterate(_comb_square, 252, 4, consts, base_m)]
    )
    powers = jnp.moveaxis(powers[:, :, 0], 0, 1)  # (NLIMBS, 64, n_bases)
    one_rows = jnp.broadcast_to(consts[NLIMBS : 2 * NLIMBS], powers.shape)
    rows = _iterate(_comb_times, 15, 1, consts, one_rows, powers)
    table = jnp.concatenate([one_rows[None], rows])  # (16, NLIMBS, 64, ·)

    # ---- items on (G / rep, n_bases * rep): an item's lane is its
    # base's lane, ``rep`` items of a base side by side ----
    rep = min(G, 1 << max(0, (_LANES // n_bases).bit_length() - 1))
    width = (G // rep, n_bases * rep)
    table = jnp.repeat(jnp.moveaxis(table, 2, 0), rep, axis=-1)
    # nibbles: exps (n_bases, G, 32) BE -> nib[k] = bits [4k, 4k+4);
    # byte 31 (LSB) supplies nibbles k=0 (low half) and k=1 (high)
    nib = jnp.stack(
        [exps_b[..., ::-1] & 0xF, exps_b[..., ::-1] >> 4], axis=-1
    ).reshape(n_bases, G // rep, rep, 64)
    nibs = jnp.transpose(nib, (3, 1, 0, 2)).reshape((64,) + width)

    out = _comb_accumulate(consts, table, nibs.astype(jnp.int32))
    out = _limbs_to_bytes33_dev(out).reshape(33, G // rep, n_bases, rep)
    return jnp.transpose(out, (2, 1, 3, 0)).reshape(n_bases, G, 33)


# (n_bases, 33) uint8 LE bases, (n_bases, G, 32) uint8 BE exponents,
# the _spec256 constants -> (n_bases, G, 33) uint8 LE
_pow_fused_grouped = _jit_program(_comb_program, 2, "_pow_fused_grouped")


@functools.cache
def _wide_kernels(lay: LimbLayout):
    """The same two programs for one wide limb family (the looped
    product), compiled once per (layout, shape): the profiler's
    modules ``jit__pow_wide`` and ``jit__dual_pow_wide``."""
    return _make_programs(lay)


def _ints_to_bytes32le(xs: Sequence[int]) -> np.ndarray:
    """(B,) ints < 2^256 -> (B, 32) uint8 little-endian (the native
    kernel's ABI layout: 4 u64 limbs)."""
    return np.frombuffer(
        b"".join(x.to_bytes(32, "little") for x in xs), dtype=np.uint8
    ).reshape(len(xs), 32)


def _bytes32le_to_ints(arr: np.ndarray) -> List[int]:
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    return [int.from_bytes(row.tobytes(), "little") for row in arr]


def _le32_to_bytes33(arr: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 little-endian (the native kernel's rows) ->
    (B, 33): the device kernels' value rows."""
    out = np.zeros((len(arr), 33), dtype=np.uint8)
    out[:, :32] = arr
    return out


def _native_modpow():
    """The compiled Montgomery kernel (native/modpow256.cpp), or None."""
    from cleisthenes_tpu.native.build import load_modpow

    return load_modpow()


def _native_scalars(modulus: int):
    """The native library for arithmetic mod ``modulus`` on byte rows,
    or None: it takes any odd modulus.  A residue is a 32-byte row, so
    no modulus over 256 bits is served at all."""
    if modulus.bit_length() > 256:
        raise ValueError("scalar rows serve moduli of at most 256 bits")
    return _native_modpow() if modulus % 2 else None


def _mod_bytes(modulus: int) -> np.ndarray:
    return np.frombuffer(modulus.to_bytes(32, "little"), dtype=np.uint8)


def mod_rows(rows: np.ndarray, modulus: int) -> np.ndarray:
    """Each ``(m, width <= 64)`` big-endian row mod ``modulus``, as
    ``(m, 32)`` big-endian rows — the scalar-field reductions of a CP
    proof (nonce mod q, challenge digest mod q) without a Python int a
    row: one native call, or ``int % modulus`` per row without the
    toolchain (or for a modulus the kernel does not take)."""
    m, width = rows.shape
    lib = _native_scalars(modulus) if width <= 64 else None
    if lib is None or m == 0:
        return ints_to_be_rows(
            [x % modulus for x in be_rows_to_ints(rows)], 32
        )
    le = np.ascontiguousarray(rows[:, ::-1])
    out = np.empty((m, 32), dtype=np.uint8)
    mod = _mod_bytes(modulus)
    lib.modreduce256_batch(
        le.ctypes.data, width, mod.ctypes.data, out.ctypes.data, m
    )
    return out[:, ::-1]


def mul_add_mod_rows(
    a: np.ndarray, b: np.ndarray, c: np.ndarray, modulus: int
) -> np.ndarray:
    """``(a * b + c) mod modulus`` row by row over ``(m, 32)``
    big-endian rows (a CP response z = w + e * s mod q; a negation
    -e = e * (q - 1)), as ``(m, 32)`` big-endian rows; native or, as
    ``mod_rows``, Python ints."""
    assert a.shape == b.shape == c.shape and a.shape[1] == 32
    m = len(a)
    lib = _native_scalars(modulus)
    if lib is None or m == 0:
        return ints_to_be_rows(
            [
                (x * y + z) % modulus
                for x, y, z in zip(
                    be_rows_to_ints(a), be_rows_to_ints(b), be_rows_to_ints(c)
                )
            ],
            32,
        )
    cols = [np.ascontiguousarray(col[:, ::-1]) for col in (a, b, c)]
    out = np.empty((m, 32), dtype=np.uint8)
    mod = _mod_bytes(modulus)
    lib.muladdmod256_batch(
        cols[0].ctypes.data, cols[1].ctypes.data, cols[2].ctypes.data,
        mod.ctypes.data, out.ctypes.data, m,
    )
    return out[:, ::-1]


class ModEngine:
    """Host-facing batched modexp over one GroupParams group.

    ``backend='tpu'`` runs the XLA Montgomery kernels: the tuned
    256-bit layout (comb + generic, one compiled program for every
    fitting group) or a wide limb family up to 2112 bits
    (layout_for_group — generic pow/dual-pow; a 2048-bit MODP or a
    384-bit BLS12-381-class field runs ON DEVICE, no cpu detour).
    ``'cpu'`` uses the native C++ Montgomery kernel
    (native/modpow256.cpp) when the toolchain builds it, else python
    pow(); a group wider than 256 bits takes OpenSSL's ladders
    (``ops.widepow``, native/modpow_wide.cpp), else python pow().
    With a ``parallel.mesh.CryptoMesh`` a batch the floors leave to
    the device shards over every mesh device flat (each
    exponentiation is sequential internally, independent across the
    batch; the comb shards its base rows, a table a row); the floors
    themselves are the one-device ones, so a wave one chip leaves on
    the native host kernel is not spread over four.
    """

    def __init__(
        self,
        backend: str = "tpu",
        mesh=None,
        group: GroupParams = DEFAULT_GROUP,
    ):
        if backend not in ("cpu", "tpu"):
            raise ValueError(backend)
        self.backend = backend
        self.mesh = mesh
        # the jax Mesh itself: a static argument of the 256-bit programs
        self._jax_mesh = None if mesh is None else mesh.mesh
        self.group = group
        self._fits256 = group.p.bit_length() <= 256 and group.p % 2 == 1
        self._layout = layout_for_group(group)
        if backend == "tpu" and self._layout is None:
            raise ValueError(
                f"tpu engine: modulus of {group.p.bit_length()} bits "
                "exceeds every XLA limb family (use backend='cpu')"
            )
        self._nat = (
            _native_modpow() if backend == "cpu" and self._fits256 else None
        )
        self._wide = None
        if backend == "tpu":
            # ops.placement family prefix: one per limb layout
            self._family = (
                f"modexp_{self._layout.limb_bits}x{self._layout.nlimbs}"
            )
            # bytes of one (value, exponent) pair as the programs take
            # them: what an int entry point reports to the tally
            self._pair_bytes = (
                33 + 32 if self._fits256 else 2 * self._layout.val_bytes
            )
            if self._fits256:
                m_limbs, m_prime, r_limbs, r2_limbs = _spec256(group)
            else:
                # wide family: generic pow/dual-pow kernels compiled
                # once per (layout, shape bucket); the comb path stays
                # 256-only and oversize groups route to these instead
                self._wide = _wide_kernels(self._layout)
                m_limbs, m_prime, r_limbs, r2_limbs = _spec_wide(
                    group, self._layout
                )
            self._xla_spec = (
                jnp.asarray(m_limbs),
                jnp.int32(m_prime),
                jnp.asarray(r_limbs),
                jnp.asarray(r2_limbs),
            )
        # keep the modulus buffer alive for the pointer's lifetime
        self._mod_arr = (
            np.frombuffer(
                group.p.to_bytes(32, "little"), dtype=np.uint8
            ).copy()
            if self._nat
            else None
        )
        self._mod32 = (
            self._mod_arr.ctypes.data if self._mod_arr is not None else None
        )

    # Batches below this many exponentiations run on the native host
    # kernel, so live-protocol quorum batches (tens to hundreds) stay
    # on the host and the device path engages on the crypto-plane and
    # lockstep waves (protocol.spmd) whose batches are 16k-500k.  The
    # value is carried over from an earlier attachment of the chip
    # and is UNMEASURED on a local one: ops.placement counts which
    # side each batch took, and PERF.md holds the dispatch cost the
    # re-derivation starts from.
    HOST_FLOOR = 8192
    # Without the compiled native kernel the host path is python
    # pow(), far slower per exponentiation — only true scalar jobs
    # stay on host then.  Carried over, unmeasured, as above.
    HOST_FLOOR_NO_NATIVE = 16
    # Per-family floors for the wide layouts (a width class must not
    # silently run on a path that loses to the host).  None means always
    # delegate to the host.  (12,32) and (11,72): carried over and
    # unmeasured on a local attachment like HOST_FLOOR, no cell runs
    # them.  (11,192), the MODP-14 group's: measured against the OpenSSL
    # host kernel (native/modpow_wide.cpp) by tools/modexpbench.py on a
    # v5e chip and its 13-core host (PERF.md section 6), ms a
    # call, pow / dual_pow, device (Pallas kernel, 1,024 rows a tile)
    # against host (13 threads):
    #       8 rows   575 / 578   against    4.1 /    4.8
    #   1,024 rows   577 / 580   against  181   /  203
    #   4,096 rows 2,303 / 2,313 against  728   /  836
    #  16,384 rows 9,216 / 9,264 against 3,159  / 3,317
    # 136 us a 192-limb product a tile: the device loses by 3.2 times or
    # more at every size, so no batch of the family goes to it.
    WIDE_FLOORS = {
        LimbLayout(12, 32): 256,
        LimbLayout(11, 72): 256,
        LimbLayout(11, 192): None,
    }
    # Class-level switch: False pins every batch to the device kernels
    # regardless of the floors.  Correctness tests exercising the XLA
    # path at small batch sizes monkeypatch this; production callers
    # never should (the floors exist because the host genuinely wins
    # below them).
    host_delegation = True

    def _host_floor(self, b: int):
        """The host engine to delegate to for tiny batches under the
        'tpu' backend, or None when the device path should run.  Mesh
        or not: the decision reads the batch, not the layout."""
        if self.backend != "tpu" or not self.host_delegation:
            return None
        host = _get_engine_nomesh("cpu", self.group)
        if self._fits256:
            floor = (
                self.HOST_FLOOR
                if host._nat is not None
                else self.HOST_FLOOR_NO_NATIVE
            )
        else:
            floor = self.WIDE_FLOORS[self._layout]
            if floor is None:
                return host  # measured: device loses at every batch
        return host if b < floor else None

    @staticmethod
    def _bucket(b: int) -> int:
        """Pad the batch axis to a power of two (min 8): the Montgomery
        exponent loop compiles per input shape (seconds each), so
        live-protocol batches — whose sizes vary with every quorum —
        must land in a handful of compile buckets, not one program
        per size."""
        bucket = 8
        while bucket < b:
            bucket <<= 1
        return bucket

    def _place(self, *arrays):
        """np arrays -> device arrays with the batch axis padded to a
        compile bucket (and the mesh's device count when sharded);
        returns (*devs, b) with b the true batch size."""
        b = len(arrays[0])
        bucket = self._rows_bucket(b)
        if bucket != b:
            arrays = tuple(
                np.concatenate(
                    [a, np.zeros((bucket - b,) + a.shape[1:], a.dtype)]
                )
                for a in arrays
            )
        return self._put(*arrays) + (b,)

    def _put(self, *arrays):
        """Host arrays on the device: as they are, or with the batch
        axis sharded flat over the mesh."""
        if self.mesh is None:
            return tuple(jnp.asarray(a) for a in arrays)
        return self.mesh.put_flat(*arrays)

    def _fetch(self, out) -> np.ndarray:
        """A program's result as a host array."""
        return host_array(self.mesh, out)

    def _rows_bucket(self, b: int) -> int:
        """``_bucket``, and a multiple of the mesh's device count when
        sharded (lcm: a (3, 2) mesh divides no power of two)."""
        bucket = self._bucket(b)
        if self.mesh is not None:
            bucket = math.lcm(bucket, self.mesh.n_devices)
        return bucket

    # The comb kernel's row width: see _comb_device.
    G_ROW = 512

    @property
    def columnar(self) -> bool:
        """Whether the byte-column entry points (``pow_grouped_cols``,
        ``pow_cols``, ``dual_pow_cols``) serve this group: their
        value columns are the 256-bit layout's 33-byte rows.  Wide
        groups keep the int entry points."""
        return self._fits256

    def _comb_plan(self, total: int) -> Tuple[bool, str]:
        """(run the comb on the device?, the ops.placement op a
        host-routed batch is tallied under) for a grouped batch of
        ``total`` exponentiations — ONE decision for the int and the
        column entry points.

        The comb crossover is lower than the generic kernel's (64 vs
        512 multiplies per exponentiation), so the floor scales by the
        same 1/6."""
        if not (
            self.backend == "tpu"
            and self._fits256  # comb tables are 256-layout only;
            # wide groups flatten to the generic wide device kernels
        ):
            return False, "pow"
        # the comb crossover scales with the host fallback's speed,
        # same native-awareness as _host_floor: with the native kernel
        # the comb wins ~6x earlier than the generic device path;
        # without it the host is python pow(), which loses almost
        # immediately
        host = _get_engine_nomesh("cpu", self.group)
        floor = (
            self.HOST_FLOOR // 6
            if host._nat is not None
            else self.HOST_FLOOR_NO_NATIVE
        )
        # a grouped batch the floor sends to the host is tallied as
        # the comb's, not the generic kernel's
        return total >= max(64, floor), "comb"

    def pow_batch_grouped(
        self, groups: Sequence[tuple]
    ) -> List[List[int]]:
        """[[base^e for e in exps] per (base, exps) group] — the
        shared-base wave shape (a protocol wave issues many shares
        against one coin base / ciphertext c1 / generator).

        On the device path this runs the fixed-base comb kernel
        (_pow_fused_grouped: 64 products an item where the ladder takes
        512; single-call times by width in PERF.md section 6, PR 30);
        below the host floor, or for oversize groups on 'cpu', it
        falls back to the generic batched path.  The int form of
        ``pow_grouped_cols``: same plan, same programs, same shapes.
        """
        if not groups:
            return []
        total = sum(len(exps) for _base, exps in groups)
        use_device, host_op = self._comb_plan(total)
        if not use_device:
            flat_b: List[int] = []
            flat_e: List[int] = []
            for base, exps in groups:
                flat_b.extend([base] * len(exps))
                flat_e.extend(exps)
            flat = self._pow_batch(flat_b, flat_e, host_op)
            out = []
            off = 0
            for _base, exps in groups:
                out.append(flat[off : off + len(exps)])
                off += len(exps)
            return out
        with trace.span("ops", "pack"):
            # same exponent domain as pow_batch: values must fit 256
            # bits (to_bytes raises loudly otherwise); no mod-q
            # reduction here — that is only sound for subgroup
            # elements and is the CALLER's knowledge, not ours
            blocks = [
                ([base], exps_to_bytes(list(exps))[None])
                for base, exps in groups
            ]
        outs = self._comb_device(blocks)
        with trace.span("ops", "unpack"):
            return [bytes33_to_ints(out[0]) for out in outs]

    def pow_grouped_cols(
        self, blocks: Sequence[tuple]
    ) -> List[np.ndarray]:
        """``pow_batch_grouped`` on byte columns.  ``blocks`` is a
        sequence of ``(bases, exps)``: ``bases`` the block's ``rows``
        int bases, ``exps`` a ``(rows, G, 32)`` uint8 big-endian
        array, every item of row r a power of ``bases[r]`` (a block is
        ``rows`` groups of one size).  Returns one ``(rows, G, 33)``
        uint8 little-endian array per block — the device's own output
        rows, never turned into Python ints."""
        self._need_columnar()
        if not blocks:
            return []
        total = sum(e.shape[0] * e.shape[1] for _b, e in blocks)
        use_device, host_op = self._comb_plan(total)
        if use_device:
            return self._comb_device(blocks)
        p = self.group.p
        flat_b = np.concatenate([
            np.repeat(
                ints_to_bytes33([b % p for b in bases]), e.shape[1], axis=0
            )
            for bases, e in blocks
        ])
        flat_e = np.concatenate([e.reshape(-1, 32) for _b, e in blocks])
        flat = self._pow_cols(flat_b, flat_e, host_op)
        outs = []
        off = 0
        for _bases, e in blocks:
            cnt = e.shape[0] * e.shape[1]
            outs.append(flat[off : off + cnt].reshape(e.shape[:2] + (33,)))
            off += cnt
        return outs

    def _comb_device(self, blocks: Sequence[tuple]) -> List[np.ndarray]:
        """The comb dispatches of a grouped batch (see
        ``pow_grouped_cols`` for ``blocks``): one per G-bucket, groups
        of similar size padded to a shared (n_bases_bucket, G_bucket)
        compile shape.

        Oversize groups are row-split: the comb table builds on the
        BASE axis (n_bases vector lanes), so one base with a huge G
        would serialize ~1.2k tiny Montgomery multiplies on a single
        lane.  Replicating the base across rows of <= G_ROW items
        re-parallelizes the build at the cost of redundant tables —
        ~2 generic-exp equivalents per extra row, noise at G >= 64."""
        gp = self.group
        outs = [
            np.empty(e.shape[:2] + (33,), dtype=np.uint8)
            for _b, e in blocks
        ]
        # (block, column offset, width): all rows of the block, its
        # exponent columns [offset, offset + width)
        by_bucket: Dict[int, List[tuple]] = {}
        with trace.span("ops", "pack"):
            base_rows = [
                ints_to_bytes33([b % gp.p for b in bases])
                for bases, _e in blocks
            ]
            for bi, (_bases, exps) in enumerate(blocks):
                g_all = exps.shape[1]
                for off in range(0, max(g_all, 1), self.G_ROW):
                    width = min(self.G_ROW, g_all - off)
                    by_bucket.setdefault(
                        self._bucket(max(1, width)), []
                    ).append((bi, off, width))
        for g_bucket, chunks in by_bucket.items():
            heights = [blocks[bi][1].shape[0] for bi, _o, _w in chunks]
            items = sum(h * w for h, (_bi, _o, w) in zip(heights, chunks))
            with placement.batch(
                self._family + ".comb",
                True,
                items,
                self.mesh,
                nbytes=33 * sum(heights) + 32 * items,  # bases, exponents
            ):
                with trace.span("ops", "pack"):
                    # under a mesh the base rows shard flat: a row's
                    # table builds where its exponents are, so the
                    # program needs no collective
                    nb = self._rows_bucket(sum(heights))
                    bases_arr = np.zeros((nb, 33), dtype=np.uint8)
                    bases_arr[:, 0] = 1  # padding bases = 1
                    exps_arr = np.zeros(
                        (nb, g_bucket, 32), dtype=np.uint8
                    )
                    row = 0
                    for h, (bi, off, width) in zip(heights, chunks):
                        bases_arr[row : row + h] = base_rows[bi]
                        exps_arr[row : row + h, :width] = blocks[bi][1][
                            :, off : off + width
                        ]
                        row += h
                with trace.span(
                    "ops", "device", program="_pow_fused_grouped"
                ):
                    out = self._fetch(
                        _pow_fused_grouped(
                            *self._put(bases_arr, exps_arr),
                            *self._xla_spec,
                            mesh=self._jax_mesh,
                        )
                    )
                with trace.span("ops", "unpack"):
                    # a group's tail slice lands in a different size
                    # bucket than its full slices: each chunk goes
                    # back to its own columns of its own block
                    row = 0
                    for h, (bi, off, width) in zip(heights, chunks):
                        outs[bi][:, off : off + width] = out[
                            row : row + h, :width
                        ]
                        row += h
        return outs

    def pow_batch(self, bases: Sequence[int], exps: Sequence[int]) -> List[int]:
        """[b_i^e_i mod p] for all i in one dispatch."""
        return self._pow_batch(bases, exps, "pow")

    def pow_cols(self, base_b: np.ndarray, exp_b: np.ndarray) -> np.ndarray:
        """``pow_batch`` on byte columns: ``(B, 33)`` uint8
        little-endian bases (values < p), ``(B, 32)`` uint8 big-endian
        exponents -> ``(B, 33)`` uint8 little-endian."""
        return self._pow_cols(base_b, exp_b, "pow")

    def _native_pow(self, base_b: np.ndarray, exp_b: np.ndarray) -> np.ndarray:
        """The native kernel on its own ABI: (B, 32) uint8
        little-endian columns in and out."""
        out = np.empty((len(base_b), 32), dtype=np.uint8)
        self._nat.modpow256_batch(
            base_b.ctypes.data,
            exp_b.ctypes.data,
            self._mod32,
            out.ctypes.data,
            len(base_b),
        )
        return out

    def _native_dual_pow(self, u1_b, e1_b, u2_b, e2_b) -> np.ndarray:
        out = np.empty((len(u1_b), 32), dtype=np.uint8)
        self._nat.dualpow256_batch(
            u1_b.ctypes.data,
            e1_b.ctypes.data,
            u2_b.ctypes.data,
            e2_b.ctypes.data,
            self._mod32,
            out.ctypes.data,
            len(u1_b),
        )
        return out

    def _pow_batch(
        self, bases: Sequence[int], exps: Sequence[int], host_op: str
    ) -> List[int]:
        """pow_batch; a batch the floor routes to the host is tallied
        (ops.placement) under ``host_op``, the op whose floor it was."""
        assert len(bases) == len(exps)
        if not bases:
            return []
        if self.backend == "cpu":
            gp = self.group.p
            if self._nat is not None:
                # reduce mod P first: python-pow semantic parity for
                # any int input (the kernel itself is exact for values
                # < 2^256, but parity must not depend on that bound);
                # out-of-range exponents raise in to_bytes — loudly
                return _bytes32le_to_ints(
                    self._native_pow(
                        _ints_to_bytes32le([b % gp for b in bases]),
                        _ints_to_bytes32le(exps),
                    )
                )
            if not self._fits256:
                return widepow.pow_rows(bases, exps, gp)
            return [pow(b, e, gp) for b, e in zip(bases, exps)]
        host = self._host_floor(len(bases))
        if host is not None:
            with placement.batch(
                f"{self._family}.{host_op}", False, len(bases),
                nbytes=len(bases) * self._pair_bytes,
            ), trace.span("ops", "host"):
                return host.pow_batch(bases, exps)
        with placement.batch(
            self._family + ".pow", True, len(bases), self.mesh,
            nbytes=len(bases) * self._pair_bytes,
        ):
            if self._wide is not None:
                lay = self._layout
                gp = self.group.p
                with trace.span("ops", "pack"):
                    cols = (
                        _ints_to_val_bytes([x % gp for x in bases], lay),
                        _exps_to_bytes_w(exps, lay),
                    )
                with trace.span("ops", "device", program="_pow_wide"):
                    base_b, exp_b, b = self._place(*cols)
                    out = self._fetch(
                        self._wide[0](base_b, exp_b, *self._xla_spec)
                    )[:b]
                with trace.span("ops", "unpack"):
                    return _val_bytes_to_ints(out)
            with trace.span("ops", "pack"):
                cols = (ints_to_bytes33(bases), exps_to_bytes(exps))
            out = self._pow_device(*cols)
            with trace.span("ops", "unpack"):
                return bytes33_to_ints(out)

    def _pow_cols(
        self, base_b: np.ndarray, exp_b: np.ndarray, host_op: str
    ) -> np.ndarray:
        """_pow_batch on byte columns: the same floor, the same
        tally, the same program."""
        self._need_columnar()
        assert len(base_b) == len(exp_b)
        if len(base_b) == 0:
            return np.empty((0, 33), dtype=np.uint8)
        if self.backend == "cpu":
            if self._nat is not None:
                return _le32_to_bytes33(
                    self._native_pow(
                        np.ascontiguousarray(base_b[:, :32]),
                        np.ascontiguousarray(exp_b[:, ::-1]),
                    )
                )
            return ints_to_bytes33(
                self._pow_batch(
                    bytes33_to_ints(base_b), be_rows_to_ints(exp_b), host_op
                )
            )
        host = self._host_floor(len(base_b))
        if host is not None:
            with placement.batch(
                f"{self._family}.{host_op}", False, len(base_b),
                nbytes=base_b.nbytes + exp_b.nbytes,
            ), trace.span("ops", "host"):
                return host.pow_cols(base_b, exp_b)
        with placement.batch(
            self._family + ".pow", True, len(base_b), self.mesh,
            nbytes=base_b.nbytes + exp_b.nbytes,
        ):
            return self._pow_device(base_b, exp_b)

    def _pow_device(self, base_b: np.ndarray, exp_b: np.ndarray) -> np.ndarray:
        with trace.span("ops", "device", program="_pow_fused"):
            base_d, exp_d, b = self._place(base_b, exp_b)
            return self._fetch(
                _pow_fused(
                    base_d, exp_d, *self._xla_spec, mesh=self._jax_mesh
                )
            )[:b]

    def _need_columnar(self) -> None:
        if not self.columnar:
            raise ValueError(
                "byte-column entry points serve the 256-bit layout "
                "only; wide groups use the int entry points"
            )

    def dual_pow_batch(
        self,
        u1: Sequence[int],
        e1: Sequence[int],
        u2: Sequence[int],
        e2: Sequence[int],
    ) -> List[int]:
        """[u1_i^e1_i * u2_i^e2_i mod p] — the CP-verification op."""
        assert len(u1) == len(e1) == len(u2) == len(e2)
        if not u1:
            return []
        if self.backend == "cpu":
            gp = self.group.p
            if self._nat is not None:
                return _bytes32le_to_ints(
                    self._native_dual_pow(
                        _ints_to_bytes32le([u % gp for u in u1]),
                        _ints_to_bytes32le(e1),
                        _ints_to_bytes32le([u % gp for u in u2]),
                        _ints_to_bytes32le(e2),
                    )
                )
            if not self._fits256:
                return widepow.dual_pow_rows(u1, e1, u2, e2, gp)
            return [
                pow(a, x, gp) * pow(b, y, gp) % gp
                for a, x, b, y in zip(u1, e1, u2, e2)
            ]
        host = self._host_floor(len(u1))
        if host is not None:
            with placement.batch(
                self._family + ".dual_pow", False, len(u1),
                nbytes=2 * len(u1) * self._pair_bytes,
            ), trace.span("ops", "host"):
                return host.dual_pow_batch(u1, e1, u2, e2)
        with placement.batch(
            self._family + ".dual_pow", True, len(u1), self.mesh,
            nbytes=2 * len(u1) * self._pair_bytes,
        ):
            if self._wide is not None:
                lay = self._layout
                gp = self.group.p
                with trace.span("ops", "pack"):
                    cols = (
                        _ints_to_val_bytes([x % gp for x in u1], lay),
                        _exps_to_bytes_w(e1, lay),
                        _ints_to_val_bytes([x % gp for x in u2], lay),
                        _exps_to_bytes_w(e2, lay),
                    )
                with trace.span("ops", "device", program="_dual_pow_wide"):
                    u1_b, e1_b, u2_b, e2_b, b = self._place(*cols)
                    out = self._fetch(
                        self._wide[1](
                            u1_b, e1_b, u2_b, e2_b, *self._xla_spec
                        )
                    )[:b]
                with trace.span("ops", "unpack"):
                    return _val_bytes_to_ints(out)
            with trace.span("ops", "pack"):
                cols = (
                    ints_to_bytes33(u1),
                    exps_to_bytes(e1),
                    ints_to_bytes33(u2),
                    exps_to_bytes(e2),
                )
            out = self._dual_pow_device(*cols)
            with trace.span("ops", "unpack"):
                return bytes33_to_ints(out)

    def dual_pow_cols(
        self,
        u1_b: np.ndarray,
        e1_b: np.ndarray,
        u2_b: np.ndarray,
        e2_b: np.ndarray,
    ) -> np.ndarray:
        """``dual_pow_batch`` on byte columns: ``(B, 33)`` uint8
        little-endian values (< p), ``(B, 32)`` uint8 big-endian
        exponents -> ``(B, 33)`` uint8 little-endian.  The same
        floor, tally and program as the int form."""
        self._need_columnar()
        assert len(u1_b) == len(e1_b) == len(u2_b) == len(e2_b)
        if len(u1_b) == 0:
            return np.empty((0, 33), dtype=np.uint8)
        if self.backend == "cpu":
            if self._nat is not None:
                return _le32_to_bytes33(
                    self._native_dual_pow(
                        np.ascontiguousarray(u1_b[:, :32]),
                        np.ascontiguousarray(e1_b[:, ::-1]),
                        np.ascontiguousarray(u2_b[:, :32]),
                        np.ascontiguousarray(e2_b[:, ::-1]),
                    )
                )
            return ints_to_bytes33(
                self.dual_pow_batch(
                    bytes33_to_ints(u1_b),
                    be_rows_to_ints(e1_b),
                    bytes33_to_ints(u2_b),
                    be_rows_to_ints(e2_b),
                )
            )
        host = self._host_floor(len(u1_b))
        nbytes = u1_b.nbytes + e1_b.nbytes + u2_b.nbytes + e2_b.nbytes
        if host is not None:
            with placement.batch(
                self._family + ".dual_pow", False, len(u1_b), nbytes=nbytes
            ), trace.span("ops", "host"):
                return host.dual_pow_cols(u1_b, e1_b, u2_b, e2_b)
        with placement.batch(
            self._family + ".dual_pow", True, len(u1_b), self.mesh,
            nbytes=nbytes,
        ):
            return self._dual_pow_device(u1_b, e1_b, u2_b, e2_b)

    def _dual_pow_device(self, u1_b, e1_b, u2_b, e2_b) -> np.ndarray:
        with trace.span("ops", "device", program="_dual_pow_fused"):
            u1_d, e1_d, u2_d, e2_d, b = self._place(u1_b, e1_b, u2_b, e2_b)
            return self._fetch(
                _dual_pow_fused(
                    u1_d, e1_d, u2_d, e2_d, *self._xla_spec,
                    mesh=self._jax_mesh,
                )
            )[:b]


@functools.cache
def _get_engine_nomesh(backend: str, group: GroupParams) -> ModEngine:
    return ModEngine(backend, group=group)


def host_pow_batch(
    bases: Sequence[int],
    exps: Sequence[int],
    group: GroupParams = DEFAULT_GROUP,
) -> List[int]:
    """Host-side [b_i^e_i mod group.p]: the native Montgomery kernel
    when it builds (~12 us/exp, <=256-bit groups), python pow()
    otherwise.  The helper every host-resident single-shot group op
    (share issuance, Lagrange combine, KEM, subgroup checks) funnels
    through so none of them sit on the CPython bignum wall."""
    return _get_engine_nomesh("cpu", group).pow_batch(
        list(bases), list(exps)
    )


def host_pow(base: int, exp: int, group: GroupParams = DEFAULT_GROUP) -> int:
    return host_pow_batch([base], [exp], group)[0]


def get_engine(
    backend: str, mesh=None, group: GroupParams = DEFAULT_GROUP
) -> ModEngine:
    """No-mesh engines are process-cached per (backend, group);
    mesh-bound engines are built per call — ModEngine is stateless, and
    caching by mesh identity would pin every CryptoMesh (and its
    device handles) alive for the process lifetime."""
    if mesh is None:
        return _get_engine_nomesh(backend, group)
    return ModEngine(backend, mesh=mesh, group=group)


__all__ = [
    "P",
    "Q",
    "G",
    "GroupParams",
    "DEFAULT_GROUP",
    "ModEngine",
    "get_engine",
    "host_pow",
    "host_pow_batch",
    "mont_mul_batch",
    "mod_rows",
    "mul_add_mod_rows",
    "LimbLayout",
    "layout_for_group",
    "xla_capable",
    "get_engine_degraded",
    "P384",
    "GROUP384",
    "int_to_limbs",
    "limbs_to_int",
]
