"""Threshold encryption (TPKE) and the generic threshold-DH core.

Implements the four-call API the reference specifies but never codes
(reference docs/THRESHOLD_ENCRYPTION-EN.md:33-36):

  TPKE.SetUp    -> ThresholdDealer / TpkeKeys (master pubkey + n shares)
  TPKE.Encrypt  -> Tpke.encrypt (hashed-ElGamal KEM under the master key)
  TPKE.DecShare -> Tpke.dec_share (share + Chaum-Pedersen validity proof)
  TPKE.Decrypt  -> Tpke.combine (Lagrange over any f+1 verified shares,
                   docs/HONEYBADGER-EN.md:40-42)

Scheme: discrete-log threshold ElGamal in the prime-order QR subgroup
of Z_p* (p a 256-bit safe prime, ops/modmath.py).  The dealer Shamir-
shares a secret s with threshold t = f+1; decryption shares are
d_i = c1^{s_i} carrying a Chaum-Pedersen NIZK (Fiat-Shamir over
SHA-256) that log_g(h_i) = log_{c1}(d_i) — so invalid shares from
Byzantine nodes are rejected before combination.  Share verification
is 2 dual-exponentiations per share, batched across all N shares in
one TPU dispatch (the "TPKE-share-verify ops/sec" BASELINE metric).

Security notes (documented, deliberate): hashed-ElGamal KEM + integrity
tag in the random-oracle model; a production deployment would swap the
group seam for a pairing curve and Baek-Zheng CCA2 or a larger prime —
the API and the batched-verify data flow are unchanged by that swap,
which is the point of the BatchCrypto seam.  The dealer is trusted
(standard for HBBFT test/bench deployments; DKG is a protocol-layer
extension).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import hmac
import operator
import secrets
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from cleisthenes_tpu.ops.hashrows import (
    be_rows_to_ints,
    ints_to_be_rows,
    sha256_rows,
)
from cleisthenes_tpu.ops.modmath import (
    DEFAULT_GROUP,
    G,
    GroupParams,
    ModEngine,
    P,
    Q,
    get_engine_degraded,
    host_pow_batch,
    ints_to_bytes33,
    mod_rows,
    mul_add_mod_rows,
)
from cleisthenes_tpu.ops import widepow
from cleisthenes_tpu.utils import trace


def _hash_to_int(*parts: bytes) -> int:
    # one pre-joined update (identical bytes to per-part updates):
    # this runs once per issued/verified share — millions of times in
    # a big lockstep epoch — and 2 C calls beat 2*len(parts)
    h = hashlib.sha256(
        b"".join(
            len(p_).to_bytes(4, "big") + p_ for p_ in parts
        )
    )
    return int.from_bytes(h.digest(), "big")


def _cp_challenge_batch(
    contexts: Sequence[bytes],
    bases: Sequence[int],
    his: Sequence[int],
    ds: Sequence[int],
    a1s: Sequence[int],
    a2s: Sequence[int],
    group: "GroupParams",
) -> List[int]:
    """All of a wave's CP challenges e = H(cp transcript) mod q in one
    batched native hash — byte-identical to mapping ``_hash_to_int``
    over the items (tests assert the equivalence).  The int form of
    ``_cp_challenge_cols``: one transcript layout for both."""
    m = len(contexts)
    if m == 0:
        return []
    with trace.span("tpke", "cp_challenge", items=m):
        nb, q = group.nbytes, group.q
        if m < 64:
            # matrix assembly costs more than it saves on the live path's
            # small hub flushes; identical bytes either way
            return [
                _hash_to_int(
                    b"cp", contexts[i], _ibytes(bases[i], nb),
                    _ibytes(his[i], nb), _ibytes(ds[i], nb),
                    _ibytes(a1s[i], nb), _ibytes(a2s[i], nb),
                )
                % q
                for i in range(m)
            ]
        digs = _cp_digest_rows(
            contexts,
            np.ones(m, dtype=np.intp),
            [ints_to_be_rows(v, nb) for v in (bases, his, ds, a1s, a2s)],
            nb,
        )
        return [e % q for e in be_rows_to_ints(digs)]


def _cp_challenge_cols(
    contexts: Sequence[bytes],
    reps,
    cols: Sequence[np.ndarray],
    group: "GroupParams",
) -> np.ndarray:
    """The CP transcripts' SHA-256 digests, ``(m, 32)`` uint8, from
    byte columns: ``contexts[j]`` covers ``reps[j]`` consecutive rows
    (a wave has one context per (base, context) pair, not per share)
    and ``cols`` are the five ``(m, nbytes)`` big-endian field columns
    (base, h_i, d, A1, A2).  The challenge is the digest mod q; row
    for row the bytes ``_hash_to_int`` hashes."""
    reps = np.broadcast_to(
        np.asarray(reps, dtype=np.intp), (len(contexts),)
    )
    with trace.span("tpke", "cp_challenge", items=int(reps.sum())):
        return _cp_digest_rows(contexts, reps, cols, group.nbytes)


def _cp_digest_rows(
    contexts: Sequence[bytes],
    reps: np.ndarray,
    cols: Sequence[np.ndarray],
    nb: int,
) -> np.ndarray:
    """Transcript rows assembled as numpy columns and digested in a
    single ctypes crossing per context length (field offsets are
    constant within a length; a lockstep wave has a handful of context
    shapes, so this stays a couple of matrix fills)."""
    m = int(reps.sum())
    head_pfx = (2).to_bytes(4, "big") + b"cp"
    heads = [head_pfx + len(c).to_bytes(4, "big") + c for c in contexts]
    by_hl: Dict[int, List[int]] = {}
    for j, h in enumerate(heads):
        by_hl.setdefault(len(h), []).append(j)
    starts = np.cumsum(reps) - reps
    field_pfx = np.frombuffer(nb.to_bytes(4, "big"), dtype=np.uint8)
    out = np.empty((m, 32), dtype=np.uint8)
    for hl, js in by_hl.items():
        head_mat = np.frombuffer(
            b"".join(heads[j] for j in js), dtype=np.uint8
        ).reshape(len(js), hl)
        rj = reps[js]
        k = int(rj.sum())
        # the rows of these runs (each run's start, counted up); one
        # context length is every row: no gather, no scatter
        sel = (
            slice(None)
            if k == m
            else np.repeat(starts[js] - (np.cumsum(rj) - rj), rj)
            + np.arange(k)
        )
        rows = np.empty((k, hl + 5 * (4 + nb)), dtype=np.uint8)
        rows[:, :hl] = np.repeat(head_mat, rj, axis=0)
        off = hl
        for col in cols:
            rows[:, off : off + 4] = field_pfx
            rows[:, off + 4 : off + 4 + nb] = col[sel]
            off += 4 + nb
        digs = sha256_rows(rows)
        if k == m:
            return digs
        out[sel] = digs
    return out


def _ibytes(x: int, nbytes: int = 32) -> bytes:
    return x.to_bytes(nbytes, "big")


# Subgroup checks since the last reset: calls, rows, and rows by the
# path their call took ("kronecker": OpenSSL's symbol; "pow": x^q).
# Read by tests and tools/modexpbench.py.
_GROUP_CHECK_FIELDS = ("calls", "rows", "kronecker", "pow")
_group_checks: Dict[str, int] = dict.fromkeys(_GROUP_CHECK_FIELDS, 0)

# Bases of the primality screen below: a probable prime to all twelve
_WITNESSES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37)


def group_check_tally() -> Dict[str, int]:
    """{calls, rows, kronecker, pow} since the last
    ``reset_group_check_tally``: ``group_members`` calls, the rows they
    checked, and those rows by the path their call took."""
    return dict(_group_checks)


def reset_group_check_tally() -> None:
    for key in _GROUP_CHECK_FIELDS:
        _group_checks[key] = 0


@functools.lru_cache(maxsize=None)
def _symbol_decides(group: GroupParams) -> bool:
    """Whether (x/p) == 1 is the same test as x^q == 1 (mod p) for
    0 < x < p: Euler's criterion makes them one where p = 2q + 1 and p
    is prime.  Primality is screened once a group: a prime p gives
    a^q = +-1 for every base a coprime to it, which a composite p
    fails for most bases."""
    p, q = group.p, group.q
    if p != 2 * q + 1 or p <= _WITNESSES[-1]:
        return False
    powers = host_pow_batch(_WITNESSES, [q] * len(_WITNESSES), group)
    return all(v in (1, p - 1) for v in powers)


def group_members(
    xs: Sequence[int], group: GroupParams = DEFAULT_GROUP
) -> List[bool]:
    """Strict membership test for the prime-order QR subgroup, a row
    each: ``1 < x < P`` and ``x^Q == 1 (mod P)``.

    Rejects 0, the identity, P-1 (the order-2 element) and every
    non-residue — the inputs a Byzantine proposer could use to make all
    honest decryption shares unverifiable forever (each honest node's
    d_i = c1^{s_i} then fails its own CP proof, burning every honest
    sender in the SharePool and stalling _maybe_commit), or to leak
    share parities via the order-2 component.

    In a safe-prime group the second condition is the Legendre symbol
    (x/P) == 1 (``_symbol_decides``), which OpenSSL computes at gcd
    speed: ~0.2 ms a 2048-bit row where x^Q takes ~2.2 ms.  Elsewhere,
    or where the kernel is missing, x^Q itself: the same answers.
    The inputs are public (a ciphertext's c1, commitments), so a
    variable-time symbol leaks nothing (docs/TPKE.md).
    """
    p = group.p
    inside = [1 < x < p for x in xs]
    cand = [x for x, ok in zip(xs, inside) if ok]
    # a member reads 1 either way: (x/P) or x^Q mod P
    found = widepow.kronecker_rows(cand, p) if _symbol_decides(group) else None
    path = "pow" if found is None else "kronecker"
    if found is None:
        found = host_pow_batch(cand, [group.q] * len(cand), group)
    _group_checks["calls"] += 1
    _group_checks["rows"] += len(xs)
    _group_checks[path] += len(xs)
    hits = iter(found)
    return [ok and next(hits) == 1 for ok in inside]


def is_group_element(x: int, group: GroupParams = DEFAULT_GROUP) -> bool:
    """``group_members`` of one element; callers run it once per
    deserialized ciphertext."""
    return group_members([x], group)[0]


def hash_to_group(data: bytes, group: GroupParams = DEFAULT_GROUP) -> int:
    """Map bytes to the QR subgroup with unknown discrete log:
    (H(data) mod p)^2 mod p."""
    x = _hash_to_int(b"h2g", data) % group.p
    if x == 0:
        x = 1
    return pow(x, 2, group.p)


# ---------------------------------------------------------------------------
# Shamir secret sharing over Z_q
# ---------------------------------------------------------------------------


def _shamir_shares(
    secret: int, n: int, threshold: int, rng_bytes, q: int = Q
) -> List[int]:
    """Evaluate a random degree-(threshold-1) polynomial with
    f(0)=secret at x = 1..n."""
    nb = max(32, (q.bit_length() + 7) // 8 + 8)  # excess bits: no bias
    coeffs = [secret] + [
        int.from_bytes(rng_bytes(nb), "big") % q for _ in range(threshold - 1)
    ]
    shares = []
    for x in range(1, n + 1):
        acc = 0
        for c in reversed(coeffs):
            acc = (acc * x + c) % q
        shares.append(acc)
    return shares


@functools.lru_cache(maxsize=4096)
def _lagrange_cached(xs: tuple, q: int) -> tuple:
    out = []
    for i, xi in enumerate(xs):
        num, den = 1, 1
        for j, xj in enumerate(xs):
            if i == j:
                continue
            num = num * xj % q
            den = den * ((xj - xi) % q) % q
        out.append(num * pow(den, -1, q) % q)
    return tuple(out)


def lagrange_coeff_at_zero(xs: Sequence[int], q: int = Q) -> List[int]:
    """lambda_i = prod_{j!=i} x_j / (x_j - x_i) mod q, for interpolation
    at 0 (Shamir recovery, docs/THRESHOLD_ENCRYPTION-EN.md:36).

    Cached by index set: an epoch combines N proposals from largely
    the SAME threshold subset of share indices, and the O(t^2) python
    coefficient loop was measurable at N=64 (t=22)."""
    return list(_lagrange_cached(tuple(xs), q))


# ---------------------------------------------------------------------------
# Generic threshold-DH: keygen, share issuance w/ CP proof, batched verify,
# Lagrange combine.  TPKE and the common coin both instantiate this.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ThresholdPublicKey:
    n: int
    threshold: int
    master: int  # h = g^s
    verification_keys: tuple  # h_i = g^{s_i}, 1-indexed by share x = i+1
    # the group every share op under this key runs in (the modulus
    # seam: a key set carries its own parameters end to end)
    group: GroupParams = DEFAULT_GROUP


@dataclasses.dataclass(frozen=True)
class ThresholdSecretShare:
    index: int  # Shamir x-coordinate (1-based)
    value: int  # s_i


class DhShare(NamedTuple):
    """d = base^{s_i} plus a Chaum-Pedersen proof (e, z) that
    log_g(h_i) == log_base(d).

    A NamedTuple, not a dataclass: a live N=64 epoch creates ~1M of
    these and frozen-dataclass ``__init__`` was a visible profile
    line."""

    index: int
    d: int
    e: int
    z: int


def _be_to_le33(be: np.ndarray) -> np.ndarray:
    """(m, nbytes <= 32) big-endian value rows -> the engine's
    (m, 33) little-endian value rows."""
    out = np.zeros((len(be), 33), dtype=np.uint8)
    out[:, : be.shape[1]] = be[:, ::-1]
    return out


def _be_rows_lt(rows: np.ndarray, bound: int) -> np.ndarray:
    """(m,) bool: each big-endian row, read as an integer, < bound."""
    m, width = rows.shape
    if bound >= 1 << (8 * width):
        return np.ones(m, dtype=bool)
    b = np.frombuffer(bound.to_bytes(width, "big"), dtype=np.uint8)
    differs = rows != b
    first = differs.argmax(axis=1)  # the most significant differing byte
    r = np.arange(m)
    return differs[r, first] & (rows[r, first] < b[first])


# How shares were issued and how many DhShare objects were made, since
# the last reset — beside ops.placement's tally (which side ran a
# batch), this one says whether a wave stayed byte columns.  Read by
# tests and tools/tracetool.py.
_TALLY_FIELDS = (
    "shares_issued_columnar",
    "shares_issued_listed",
    "shares_materialized",
)
_tally: Dict[str, int] = dict.fromkeys(_TALLY_FIELDS, 0)


def share_tally() -> Dict[str, int]:
    """{shares_issued_columnar, shares_issued_listed,
    shares_materialized}: shares issued as ``ShareColumns`` rows,
    shares issued as ``DhShare`` lists, and ``DhShare`` objects made
    (by a list issue or by ``ShareColumns.to_shares``)."""
    return dict(_tally)


def reset_share_tally() -> None:
    for key in _TALLY_FIELDS:
        _tally[key] = 0


class ShareColumns:
    """A wave of threshold shares as numpy byte columns: ``index``
    ``(m,)`` int32 and ``d``, ``e``, ``z`` ``(m, group.nbytes)`` uint8
    big-endian (the transcript's and the wire's byte order), from the
    moment the device returns them until they go back to it.  A slice
    is a view of the same rows; a ``DhShare`` is made only by
    ``to_shares``, for rows some caller reads one by one."""

    __slots__ = ("group", "index", "d", "e", "z")

    def __init__(
        self,
        group: GroupParams,
        index: np.ndarray,
        d: np.ndarray,
        e: np.ndarray,
        z: np.ndarray,
    ):
        self.group = group
        self.index = index
        self.d = d
        self.e = e
        self.z = z

    def __len__(self) -> int:
        return len(self.index)

    def __getitem__(self, rows) -> "ShareColumns":
        """The rows a slice (or an index array) names."""
        return ShareColumns(
            self.group,
            self.index[rows],
            self.d[rows],
            self.e[rows],
            self.z[rows],
        )

    def to_shares(self) -> List[DhShare]:
        _tally["shares_materialized"] += len(self)
        return [
            DhShare(i, d, e, z)
            for i, d, e, z in zip(
                self.index.tolist(),
                be_rows_to_ints(self.d),
                be_rows_to_ints(self.e),
                be_rows_to_ints(self.z),
            )
        ]

    @classmethod
    def from_shares(
        cls, shares: Sequence[DhShare], group: GroupParams = DEFAULT_GROUP
    ) -> "ShareColumns":
        nb = group.nbytes
        return cls(
            group,
            np.asarray([s.index for s in shares], dtype=np.int32),
            ints_to_be_rows([s.d for s in shares], nb),
            ints_to_be_rows([s.e for s in shares], nb),
            ints_to_be_rows([s.z for s in shares], nb),
        )


class ShareWave(NamedTuple):
    """A rectangular issue wave: every one of ``secrets`` (with its
    verification key in ``vks``, same order) issues a share on every
    ``(base, context)`` of ``pairs``."""

    secrets: Sequence[ThresholdSecretShare]
    vks: Sequence[int]
    pairs: Sequence[Tuple[int, bytes]]


def deal(
    n: int,
    threshold: int,
    seed: Optional[int] = None,
    group: GroupParams = DEFAULT_GROUP,
) -> tuple:
    """Trusted-dealer setup (TPKE.SetUp): master pubkey + n secret
    shares.  Deterministic iff ``seed`` given (tests/benchmarks)."""
    if seed is not None:
        ctr = [0]

        def rng_bytes(k: int) -> bytes:
            out = b""
            while len(out) < k:  # k may exceed one digest (large groups)
                ctr[0] += 1
                out += hashlib.sha256(
                    b"dealer|%d|%d" % (seed, ctr[0])
                ).digest()
            return out[:k]

    else:
        rng_bytes = secrets.token_bytes  # staticcheck: allow[DET001] unseeded dealer keygen
    # 8 excess bytes: the reduction mod q is statistically unbiased
    # (bias < 2^-64), matching _shamir_shares' rule
    s = int.from_bytes(rng_bytes(group.nbytes + 8), "big") % group.q
    shares = _shamir_shares(s, n, threshold, rng_bytes, group.q)
    vks = host_pow_batch([group.g] * (n + 1), [s] + shares, group)
    pub = ThresholdPublicKey(
        n=n,
        threshold=threshold,
        master=vks[0],
        verification_keys=tuple(vks[1:]),
        group=group,
    )
    return pub, [
        ThresholdSecretShare(index=i + 1, value=si)
        for i, si in enumerate(shares)
    ]


def issue_share(
    share: ThresholdSecretShare,
    base: int,
    context: bytes,
    group: GroupParams = DEFAULT_GROUP,
) -> DhShare:
    """d = base^{s_i} with CP proof bound to ``context``."""
    # 8 excess bytes -> unbiased nonce: a biased Schnorr/CP nonce
    # leaks the secret share to a lattice (hidden-number) attack over
    # many observed shares, since z = w + e*s_i is linear in w
    nonce = secrets.token_bytes(  # staticcheck: allow[DET001] CP-proof nonce
        group.nbytes + 8
    )
    w = int.from_bytes(nonce, "big") % group.q
    a1, a2, hi, d = host_pow_batch(
        [group.g, base, group.g, base],
        [w, w, share.value, share.value],
        group,
    )
    nb = group.nbytes
    e = (
        _hash_to_int(
            b"cp", context, _ibytes(base, nb), _ibytes(hi, nb),
            _ibytes(d, nb), _ibytes(a1, nb), _ibytes(a2, nb),
        )
        % group.q
    )
    z = (w + e * share.value) % group.q
    return DhShare(index=share.index, d=d, e=e, z=z)


def _cp_nonce_rows(m: int, group: GroupParams) -> np.ndarray:
    """A wave's m CP-proof nonces before reduction, ``(m, nbytes + 8)``
    big-endian rows from ONE urandom draw (a lockstep wave issues ~N^2
    shares; per-item token_bytes was one syscall each) — the unbiased
    nonce rule (and reason) of issue_share: w = row mod q."""
    stride = group.nbytes + 8
    pool = secrets.token_bytes(  # staticcheck: allow[DET001] CP-proof nonces
        stride * m
    )
    return np.frombuffer(pool, dtype=np.uint8).reshape(m, stride)


def issue_shares_batch(
    items: Sequence[tuple],
    group: GroupParams = DEFAULT_GROUP,
    backend: str = "cpu",
    mesh=None,
) -> List[DhShare]:
    """Issue MANY shares in one batched exponentiation dispatch.

    ``items``: sequence of ``(share, base, context, vk)`` — ``vk`` is
    the issuer's public verification key g^{s_i} (``None`` recomputes
    it, costing one extra exponentiation per item).  Semantics match
    ``issue_share`` exactly, in one dispatch instead of one
    4-exponentiation batch per share.  The served path's entry point
    (CryptoHub, HoneyBadger, ``Tpke.dec_share_batch``,
    ``coin.share_batch``): its callers read the shares one by one, so
    it returns ``DhShare`` objects; ``issue_share_columns`` is the
    lockstep executor's, whose waves stay byte columns.
    """
    if not items:
        return []
    with trace.span(
        "tpke",
        "issue_batch",
        items=len(items),
        columnar=False,
        materialized=len(items),
    ):
        return _issue_listed(
            items, group, get_engine_degraded(backend, mesh, group)
        )


def _issue_listed(
    items: Sequence[tuple], group: GroupParams, eng
) -> List[DhShare]:
    """The list issue: Python ints through the engine's int entry
    points, one ``DhShare`` per item."""
    _tally["shares_issued_listed"] += len(items)
    _tally["shares_materialized"] += len(items)
    q, g = group.q, group.g
    # Exponentiations grouped by base — a wave shares a handful of
    # bases (the generator g plus one coin base / ciphertext c1 per
    # instance), which is exactly the fixed-base comb kernel's shape
    # (ModEngine.pow_batch_grouped).
    ws = [w % q for w in be_rows_to_ints(_cp_nonce_rows(len(items), group))]
    g_exps: List[int] = []
    by_base: Dict[int, List[int]] = {}
    for (share, base, _context, vk), w in zip(items, ws):
        g_exps.append(w)  # a1 = g^w
        if vk is None:
            g_exps.append(share.value)  # h_i = g^{s_i}
        be = by_base.setdefault(base, [])
        be.append(w)  # a2 = base^w
        be.append(share.value)  # d = base^{s_i}
    base_order = list(by_base)
    groups = [(g, g_exps)] + [(b, by_base[b]) for b in base_order]
    pows = eng.pow_batch_grouped(groups)
    g_res = pows[0]
    base_res = {b: res for b, res in zip(base_order, pows[1:])}
    base_off = {b: 0 for b in base_order}
    g_off = 0
    a1s: List[int] = []
    his: List[int] = []
    a2s: List[int] = []
    ds: List[int] = []
    for share, base, _context, vk in items:
        a1s.append(g_res[g_off])
        g_off += 1
        if vk is None:
            his.append(g_res[g_off])
            g_off += 1
        else:
            his.append(vk)
        bo = base_off[base]
        a2s.append(base_res[base][bo])
        ds.append(base_res[base][bo + 1])
        base_off[base] = bo + 2
    es = _cp_challenge_batch(
        [it[2] for it in items],
        [it[1] for it in items],
        his,
        ds,
        a1s,
        a2s,
        group,
    )
    return [
        DhShare(
            index=share.index,
            d=d,
            e=e,
            z=(w + e * share.value) % q,
        )
        for (share, _b, _c, _vk), w, d, e in zip(items, ws, ds, es)
    ]


def issue_share_columns(
    waves: Sequence[ShareWave],
    group: GroupParams = DEFAULT_GROUP,
    backend: str = "cpu",
    mesh=None,
) -> ShareColumns:
    """``issue_shares_batch`` for rectangular waves, as byte columns:
    the same shares with the same CP proofs in the same exponentiation
    dispatch, returned as one ``ShareColumns`` — wave after wave, pair
    after pair, issuer after issuer (row ``j * n + i`` of a wave is
    ``secrets[i]``'s share on ``pairs[j]``).  The lockstep executor's
    path: its waves are (coin ids or ciphertexts) x nodes.

    A group the engine's column entry points do not serve (a wide
    layout) is issued through the list body and packed."""
    m = sum(len(wave.secrets) * len(wave.pairs) for wave in waves)
    if m == 0:
        return ShareColumns.from_shares([], group)
    eng = get_engine_degraded(backend, mesh, group)
    with trace.span(
        "tpke",
        "issue_batch",
        items=m,
        columnar=eng.columnar,
        materialized=0 if eng.columnar else m,
    ):
        if eng.columnar:
            return _issue_columns(waves, m, group, eng)
        return ShareColumns.from_shares(
            _issue_listed(
                [
                    (sec, base, context, vk)
                    for wave in waves
                    for base, context in wave.pairs
                    for sec, vk in zip(wave.secrets, wave.vks)
                ],
                group,
                eng,
            ),
            group,
        )


def _issue_columns(
    waves: Sequence[ShareWave], m: int, group: GroupParams, eng
) -> ShareColumns:
    _tally["shares_issued_columnar"] += m
    q, nb = group.q, group.nbytes
    w_col = mod_rows(_cp_nonce_rows(m, group), q)
    # Exponentiations grouped by base, the fixed-base comb kernel's
    # shape (ModEngine.pow_grouped_cols): g^w for every share, then
    # per pair base^w (a2) and base^{s_i} (d), n of each
    blocks = [([group.g], w_col[None])]
    s_col = np.empty((m, 32), dtype=np.uint8)  # each row's secret
    row = 0
    for wave in waves:
        n, k = len(wave.secrets), len(wave.pairs)
        rows = slice(row, row + k * n)
        s_col[rows] = np.tile(
            ints_to_be_rows([s.value for s in wave.secrets], 32), (k, 1)
        )
        exps = np.empty((k, 2 * n, 32), dtype=np.uint8)
        exps[:, :n] = w_col[rows].reshape(k, n, 32)
        exps[:, n:] = s_col[rows].reshape(k, n, 32)
        blocks.append(([base for base, _c in wave.pairs], exps))
        row += k * n
    pows = eng.pow_grouped_cols(blocks)
    # the device's little-endian rows, read as the transcript's
    # big-endian columns: views, copied once into the hash matrix
    a1 = pows[0][0][:, nb - 1 :: -1]
    base_col = np.empty((m, nb), dtype=np.uint8)
    hi_col = np.empty((m, nb), dtype=np.uint8)
    a2 = np.empty((m, nb), dtype=np.uint8)
    d = np.empty((m, nb), dtype=np.uint8)
    index = np.empty(m, dtype=np.int32)
    contexts: List[bytes] = []
    reps: List[int] = []
    row = 0
    for wave, res in zip(waves, pows[1:]):
        n, k = len(wave.secrets), len(wave.pairs)
        rows = slice(row, row + k * n)
        base_col[rows] = np.repeat(
            ints_to_be_rows([base for base, _c in wave.pairs], nb),
            n,
            axis=0,
        )
        hi_col[rows] = np.tile(ints_to_be_rows(wave.vks, nb), (k, 1))
        a2[rows] = res[:, :n, nb - 1 :: -1].reshape(k * n, nb)
        d[rows] = res[:, n:, nb - 1 :: -1].reshape(k * n, nb)
        index[rows] = np.tile(
            np.asarray([s.index for s in wave.secrets], dtype=np.int32), k
        )
        contexts.extend(context for _b, context in wave.pairs)
        reps.extend([n] * k)
        row += k * n
    digs = _cp_challenge_cols(
        contexts, reps, (base_col, hi_col, d, a1, a2), group
    )
    # e = digest mod q and z = w + e * s_i mod q for every share,
    # verified or not: each carries its whole proof
    e = mod_rows(digs, q)
    z = mul_add_mod_rows(e, s_col, w_col, q)
    return ShareColumns(group, index, d, e[:, 32 - nb :], z[:, 32 - nb :])


def _combine_subset(shares, threshold: int) -> Tuple[List[int], List[int]]:
    """(indices, d values) of the subset a combine uses: the first
    ``threshold`` shares by Shamir index — of a ``DhShare`` sequence
    or of ``ShareColumns`` rows, the same ints either way (so the two
    forms share memo entries)."""
    if len(shares) < threshold:
        raise ValueError(
            f"need >= {threshold} shares to combine, got {len(shares)}"
        )
    if isinstance(shares, ShareColumns):
        order = np.argsort(shares.index, kind="stable")[:threshold]
        xs = shares.index[order].tolist()
        ds = be_rows_to_ints(shares.d[order])
    else:
        use = sorted(shares, key=_share_index)[:threshold]
        xs = [s.index for s in use]
        ds = [s.d for s in use]
    if len(set(xs)) != len(xs):
        raise ValueError("duplicate share indices")
    return xs, ds


_share_index = operator.attrgetter("index")

# Rows of a combine wave go to the engine this many at a time, and a
# last chunk of half as many or more is padded up to it: the
# exponentiation programs compile per row bucket (ModEngine._bucket),
# and a served wave is anything from one set to a roster's N^2 sets, so
# unchunked it would meet a new power of two (a compilation of seconds)
# whenever a round revealed more coins at once than any round before.
# Chunked at twice the engine's host floor, every chunk the floor
# leaves to the device has the one shape; smaller tails stay on the
# host kernel.
COMBINE_CHUNK_ROWS = 2 * ModEngine.HOST_FLOOR


def _pow_chunks(eng, bases: List[int], index_sets: List[tuple], q: int):
    """``bases ** (the Lagrange coefficients of each index set, in
    turn)`` as Python ints, plus the number of engine calls made,
    ``COMBINE_CHUNK_ROWS`` rows at a time: byte columns through
    ``pow_cols`` where the engine is columnar (the exponents are the
    cached ``(t, 32)`` rows, so none is converted), the int entry
    point otherwise."""
    n = len(bases)
    step = COMBINE_CHUNK_ROWS
    if not eng.columnar:
        exps = [lam for xs in index_sets for lam in _lagrange_cached(xs, q)]
        terms: List[int] = []
        for lo in range(0, n, step):
            terms.extend(
                eng.pow_batch(bases[lo : lo + step], exps[lo : lo + step])
            )
        return terms, -(-n // step)
    base_b = ints_to_bytes33(bases)
    exp_b = np.concatenate([_lagrange_exp_rows(xs, q) for xs in index_sets])
    outs = []
    for lo in range(0, n, step):
        b, e = base_b[lo : lo + step], exp_b[lo : lo + step]
        rows = len(b)
        if rows < step <= 2 * rows:
            # 0 ** 0 rows: the tail takes the full chunks' shape
            pad = step - rows
            b = np.concatenate([b, np.zeros((pad, 33), dtype=np.uint8)])
            e = np.concatenate([e, np.zeros((pad, 32), dtype=np.uint8)])
        outs.append(eng.pow_cols(b, e)[:rows])
    out = outs[0] if len(outs) == 1 else np.concatenate(outs)
    return be_rows_to_ints(out[:, ::-1]), len(outs)


def combine_share_wave(
    share_sets: Sequence[Sequence[DhShare]],
    thresholds: Sequence[int],
    group: GroupParams = DEFAULT_GROUP,
    backend: str = "cpu",
    mesh=None,
) -> Tuple[List[int], int, int]:
    """``combine_shares_batch`` with a threshold a set (the rows of
    one dispatch do not care whose roster they are) and its tally:
    ``(values, exponentiation dispatches made, sets answered without
    one)`` — the last being memo hits and sets repeated within the
    wave (a cluster-shared hub sees validators offer the same
    subset)."""
    if not share_sets:
        return [], 0, 0
    with trace.span("tpke", "combine_batch", groups=len(share_sets)):
        eng = get_engine_degraded(backend, mesh, group)
        p, q = group.p, group.q
        results: List[Optional[int]] = [None] * len(share_sets)
        fresh: Dict[tuple, List[int]] = {}  # memo key -> result slots
        bases: List[int] = []
        index_sets: List[tuple] = []
        for si, (shares, threshold) in enumerate(
            zip(share_sets, thresholds)
        ):
            xs, ds = _combine_subset(shares, threshold)
            key = (group, threshold, tuple(zip(xs, ds)))
            hit = _COMBINE_MEMO.get(key)
            if hit is not None:
                results[si] = hit
                continue
            slots = fresh.get(key)
            if slots is not None:
                slots.append(si)
                continue
            fresh[key] = [si]
            bases.extend([d % p for d in ds])
            index_sets.append(tuple(xs))
        dispatches = 0
        if fresh:
            terms, dispatches = _pow_chunks(eng, bases, index_sets, q)
            off = 0
            for (key, slots), xs in zip(fresh.items(), index_sets):
                acc = 1
                for term in terms[off : off + len(xs)]:
                    acc = acc * term % p
                off += len(xs)
                if len(_COMBINE_MEMO) >= _COMBINE_MEMO_CAP:
                    _COMBINE_MEMO.clear()
                _COMBINE_MEMO[key] = acc
                for si in slots:
                    results[si] = acc
        return (  # type: ignore[return-value]
            results,
            dispatches,
            len(share_sets) - len(fresh),
        )


def combine_shares_batch(
    share_sets: Sequence[Sequence[DhShare]],
    threshold: int,
    group: GroupParams = DEFAULT_GROUP,
    backend: str = "cpu",
    mesh=None,
) -> List[int]:
    """Lagrange-combine many independent share sets in ONE
    exponentiation dispatch (each set >= threshold verified shares;
    result order matches input order).  Equivalent to mapping
    ``combine_shares``, and shares its memo."""
    return combine_share_wave(
        share_sets, [threshold] * len(share_sets), group, backend, mesh
    )[0]


def verify_share_groups(
    groups: Sequence[tuple],
    backend: str = "cpu",
    mesh=None,
) -> List[List[bool]]:
    """Batched CP verification across heterogeneous groups.

    ``groups`` is a sequence of ``(pub, base, shares, context)`` — e.g.
    one group per (proposer ciphertext) or per (BBA instance, round)
    coin — and ALL of their CP proofs run as ONE dual-exponentiation
    dispatch: recompute A1 = g^z * h_i^{-e}, A2 = base^z * d^{-e},
    accept iff e == H(transcript).  This is the cross-instance batching
    the protocol hub uses: an epoch's N TPKE ciphertexts and its
    concurrent BBA coins verify together instead of one dispatch per
    instance (the reference's cost model is 4N^2 shares/epoch,
    docs/HONEYBADGER-EN.md:93-94).
    """
    if not groups:
        return []
    with trace.span("tpke", "verify_batch", groups=len(groups)):
        # one engine (and one batched dispatch) per distinct GroupParams;
        # in practice a node's TPKE and coin keys share one group, so this
        # stays a single dispatch
        by_gp: Dict[GroupParams, List[int]] = {}
        for gi, (pub, _base, _shares, _context) in enumerate(groups):
            by_gp.setdefault(pub.group, []).append(gi)
        results: Dict[int, List[bool]] = {}
        for gp, idx_list in by_gp.items():
            eng = get_engine_degraded(backend, mesh, gp)
            # NOTE: a comb-decomposed variant (g^z, h^{-e}, base^z grouped
            # fixed-base; d^{-e} generic; host recombination) was once
            # measured SLOWER than this fused path at 4k checks (on an
            # earlier attachment of the chip): Shamir's trick already
            # shares the square chain between both factors of each dual,
            # so the decomposition saves fewer multiplies than it spends
            # on extra dispatches and host marshalling.
            a = _verify_pows_dual(gp, eng, groups, idx_list)
            results.update(_cp_verdicts(gp, groups, idx_list, a))
        return [results[gi] for gi in range(len(groups))]


def _verify_dual_items(gp, groups, idx_list):
    """The (u1, e1, u2, e2) dual-exponentiation lists recomputing
    (A1, A2) for every share of ``idx_list``'s groups — shared by the
    plain and the fused verifiers so the two can never drift."""
    u1, e1, u2, e2 = [], [], [], []
    for gi in idx_list:
        pub, base, shares, _context = groups[gi]
        for sh in shares:
            if not (1 <= sh.index <= pub.n):
                # out-of-roster index: verified vacuously false by
                # pinning to vk=1 (never matches a real transcript)
                hi = 1
            else:
                hi = pub.verification_keys[sh.index - 1]
            neg_e = (-sh.e) % gp.q
            # A1 = g^z * hi^{-e}
            u1.append(gp.g); e1.append(sh.z % gp.q)
            u2.append(hi); e2.append(neg_e)
            # A2 = base^z * d^{-e}
            u1.append(base); e1.append(sh.z % gp.q)
            u2.append(sh.d % gp.p); e2.append(neg_e)
    return u1, e1, u2, e2


def _cp_verdicts(gp, groups, idx_list, a) -> Dict[int, List[bool]]:
    """Verdicts from the recomputed (A1, A2) stream ``a`` (two entries
    per share, idx_list order): assemble every transcript, run ONE
    batched challenge hash, compare — shared by the plain and fused
    verifiers."""
    off = 0
    ctxs: List[bytes] = []
    basel: List[int] = []
    hil: List[int] = []
    dl: List[int] = []
    a1l: List[int] = []
    a2l: List[int] = []
    struct_ok: List[bool] = []
    want_e: List[int] = []
    for gi in idx_list:
        pub, base, shares, context = groups[gi]
        for sh in shares:
            a1, a2 = a[off], a[off + 1]
            off += 2
            ok = (1 <= sh.index <= pub.n) and (0 < sh.d < gp.p)
            hi = pub.verification_keys[sh.index - 1] if ok else 1
            ctxs.append(context)
            basel.append(base)
            hil.append(hi)
            dl.append(sh.d % gp.p)
            a1l.append(a1)
            a2l.append(a2)
            struct_ok.append(ok)
            want_e.append(sh.e % gp.q)
    es = _cp_challenge_batch(ctxs, basel, hil, dl, a1l, a2l, gp)
    results: Dict[int, List[bool]] = {}
    k = 0
    for gi in idx_list:
        _pub, _base, shares, _context = groups[gi]
        res = []
        for _sh in shares:
            res.append(struct_ok[k] and es[k] == want_e[k])
            k += 1
        results[gi] = res
    return results


def _verify_pows_dual(gp, eng, groups, idx_list) -> List[int]:
    """(A1, A2) per share via the fused dual-exponentiation kernel —
    the host path and the small-batch device path."""
    u1, e1, u2, e2 = _verify_dual_items(gp, groups, idx_list)
    return eng.dual_pow_batch(u1, e1, u2, e2)


def _as_share_list(shares) -> Sequence[DhShare]:
    return shares.to_shares() if isinstance(shares, ShareColumns) else shares


@functools.lru_cache(maxsize=4096)
def _lagrange_exp_rows(xs: tuple, q: int) -> np.ndarray:
    """The Lagrange coefficients of ``xs`` as the engine's (t, 32)
    big-endian exponent rows (a wave combines the same index set for
    every instance)."""
    return ints_to_be_rows(_lagrange_cached(xs, q), 32)


def _verify_combine_columns(
    gp: GroupParams,
    eng,
    groups: Sequence[tuple],
    threshold: int,
    combine_only_sets: Sequence["ShareColumns"],
) -> Tuple[List[List[bool]], List[Optional[int]], List[int]]:
    """``verify_and_combine_share_groups`` for one GroupParams on byte
    columns: the list form's checks and its one dual-exponentiation
    dispatch (A1 rows, A2 rows, then the combine terms as u2^0 = 1
    duals), with every share's d, e, z going to the device, and every
    recomputed (A1, A2) into the transcript rows, as the bytes they
    already are."""
    nb, p, q = gp.nbytes, gp.p, gp.q
    counts = np.asarray([len(g[2]) for g in groups], dtype=np.intp)
    n_ver = int(counts.sum())
    empty = ShareColumns.from_shares([], gp)  # concatenate needs one
    cols = [empty] + [g[2] for g in groups]
    index = np.concatenate([c.index for c in cols])
    d = np.concatenate([c.d for c in cols])
    e = np.concatenate([c.e for c in cols])
    z = np.concatenate([c.z for c in cols])
    # structural checks: 1 <= index <= n, 0 < d < p; an out-of-roster
    # index is verified vacuously false by pinning to vk = 1 (never
    # matches a real transcript)
    pubs: Dict[int, ThresholdPublicKey] = {}  # by identity, first seen
    for pub, _base, _shares, _context in groups:
        pubs.setdefault(id(pub), pub)
    pub_no = {key: k for k, key in enumerate(pubs)}
    # one table of every key set's [1, vk_1 .. vk_n]
    vk_rows = [[1, *pub.verification_keys] for pub in pubs.values()]
    vk_table = ints_to_be_rows(
        [vk for rows in vk_rows for vk in rows], nb
    )
    vk_off = np.cumsum([0] + [len(rows) for rows in vk_rows], dtype=np.intp)
    pub_row = np.repeat(
        np.asarray([pub_no[id(g[0])] for g in groups], dtype=np.intp), counts
    )
    n_row = np.asarray([pub.n for pub in pubs.values()], dtype=np.intp)[
        pub_row
    ]
    in_roster = (index >= 1) & (index <= n_row)
    hi = vk_table[vk_off[pub_row] + np.where(in_roster, index, 0)]
    struct_ok = in_roster & d.any(axis=1) & _be_rows_lt(d, p)
    # the list verifier's d % p, z % q, e % q and -e % q, as byte rows
    d = mod_rows(d, p)[:, 32 - nb :]
    z32 = mod_rows(z, q)
    e32 = mod_rows(e, q)
    neg_e = mul_add_mod_rows(
        e32,
        np.tile(ints_to_be_rows([q - 1], 32), (n_ver, 1)),
        np.zeros((n_ver, 32), dtype=np.uint8),
        q,
    )
    base_col = np.repeat(
        ints_to_be_rows([g[1] for g in groups], nb), counts, axis=0
    )

    # combine terms: memo hits now, the rest queued on the dispatch
    values: List[Optional[int]] = [None] * len(groups)
    co_values: List[int] = [0] * len(combine_only_sets)
    queued: List[tuple] = []  # (store, slot, memo key)
    term_d: List[int] = []
    term_lam: List[np.ndarray] = []

    def queue_combine(shares, store, slot) -> None:
        xs, ds = _combine_subset(shares, threshold)
        key = (gp, threshold, tuple(zip(xs, ds)))
        hit = _COMBINE_MEMO.get(key)
        if hit is not None:
            store[slot] = hit
            return
        term_d.extend(x % p for x in ds)
        term_lam.append(_lagrange_exp_rows(tuple(xs), q))
        queued.append((store, slot, key))

    for gi, (_pub, _base, shares, _context) in enumerate(groups):
        if len(shares) >= threshold:
            queue_combine(shares, values, gi)
    for ci, shares in enumerate(combine_only_sets):
        queue_combine(shares, co_values, ci)

    n_term = len(term_d)
    total = 2 * n_ver + n_term
    u1 = np.zeros((total, 33), dtype=np.uint8)
    e1 = np.zeros((total, 32), dtype=np.uint8)
    u2 = np.zeros((total, 33), dtype=np.uint8)
    e2 = np.zeros((total, 32), dtype=np.uint8)
    # A1 = g^z * hi^{-e}
    u1[:n_ver] = _be_to_le33(ints_to_be_rows([gp.g], nb))
    e1[:n_ver] = z32
    u2[:n_ver] = _be_to_le33(hi)
    e2[:n_ver] = neg_e
    # A2 = base^z * d^{-e}
    u1[n_ver : 2 * n_ver] = _be_to_le33(base_col)
    e1[n_ver : 2 * n_ver] = z32
    u2[n_ver : 2 * n_ver] = _be_to_le33(d)
    e2[n_ver : 2 * n_ver] = neg_e
    # d^lambda * 1^0
    if n_term:
        u1[2 * n_ver :] = _be_to_le33(ints_to_be_rows(term_d, nb))
        e1[2 * n_ver :] = np.concatenate(term_lam)
        u2[2 * n_ver :, 0] = 1
    a = eng.dual_pow_cols(u1, e1, u2, e2)

    digs = _cp_challenge_cols(
        [g[3] for g in groups],
        counts,
        (
            base_col,
            hi,
            d,
            a[:n_ver, nb - 1 :: -1],
            a[n_ver : 2 * n_ver, nb - 1 :: -1],
        ),
        gp,
    )
    match = (mod_rows(digs, q) == e32).all(axis=1)
    ok = struct_ok & match
    verdicts = [
        v.tolist() for v in np.split(ok, np.cumsum(counts))[:-1]
    ]

    terms = be_rows_to_ints(a[2 * n_ver :, nb - 1 :: -1])
    off = 0
    for store, slot, key in queued:
        acc = 1
        for term in terms[off : off + threshold]:
            acc = acc * term % p
        off += threshold
        if len(_COMBINE_MEMO) >= _COMBINE_MEMO_CAP:
            _COMBINE_MEMO.clear()
        _COMBINE_MEMO[key] = acc
        store[slot] = acc
    return verdicts, values, co_values


def verify_and_combine_share_groups(
    groups: Sequence[tuple],
    threshold: int,
    backend: str = "cpu",
    mesh=None,
    combine_only_sets: Sequence[Sequence[DhShare]] = (),
    combine_only_group: Optional[GroupParams] = None,
) -> Tuple[List[List[bool]], List[Optional[int]], List[int]]:
    """Verify every group's CP proofs AND Lagrange-combine each group's
    first ``threshold`` shares in ONE fused dual-exponentiation
    dispatch (half the device round-trips of verify + combine run
    separately — the lockstep BBA's per-round critical path).

    ``groups`` is ``(pub, base, shares, context)`` as in
    ``verify_share_groups``, each ``shares`` a ``DhShare`` sequence or
    a ``ShareColumns`` slice; returns ``(verdicts, values,
    combine_only_values)`` where ``values[i]`` is the combination of
    group i's shares (``None`` when the group has fewer than
    ``threshold`` shares).  When every ``shares`` is ``ShareColumns``
    (and the engine serves columns) the wave stays byte columns from
    here to the device and back: no ``DhShare`` is made.  Combination
    does not wait for the verdicts — callers must discard the value
    of any group whose verdicts fail (the lockstep executor asserts
    them; the live path uses the unfused ops).  Results seed the
    combine memo, so a later ``combine_shares`` on the same subset is
    a pure host hit.

    ``combine_only_sets`` are additional share sets (same threshold,
    group ``combine_only_group`` — defaults to the first group's) to
    Lagrange-combine WITHOUT verification in the same dispatch: the
    lockstep executor rides its whole optimistic-decrypt wave on BBA
    round 0's device round-trip this way.  Their values are the third
    returned list."""
    if not groups and not combine_only_sets:
        return [], [], []
    with trace.span(
        "tpke",
        "verify_combine_batch",
        groups=len(groups),
        combine_only=len(combine_only_sets),
    ) as sp:
        made = _tally["shares_materialized"]
        columnar = all(
            isinstance(g[2], ShareColumns) for g in groups
        ) and all(isinstance(cs, ShareColumns) for cs in combine_only_sets)
        if columnar:
            gps = [g[0].group for g in groups]
            if combine_only_group is not None:
                gps.append(combine_only_group)
            columnar = all(
                get_engine_degraded(backend, mesh, gp).columnar
                for gp in dict.fromkeys(gps)
            )
        if not columnar:
            # mixed or wide: the list form, on materialised rows
            groups = [
                (pub, base, _as_share_list(shares), context)
                for pub, base, shares, context in groups
            ]
            combine_only_sets = [
                _as_share_list(cs) for cs in combine_only_sets
            ]
        sp.note(
            columnar=columnar,
            materialized=_tally["shares_materialized"] - made,
        )
        by_gp: Dict[GroupParams, List[int]] = {}
        for gi, (pub, _base, _shares, _context) in enumerate(groups):
            by_gp.setdefault(pub.group, []).append(gi)
        co_gp: Optional[GroupParams] = None
        if combine_only_sets:
            if combine_only_group is not None:
                co_gp = combine_only_group
            elif groups:
                co_gp = groups[0][0].group
            else:
                # guessing a group here would produce a well-formed but
                # cryptographically WRONG combination (and memoize it)
                raise ValueError(
                    "combine_only_sets without groups requires an "
                    "explicit combine_only_group"
                )
            by_gp.setdefault(co_gp, [])
        verdicts: Dict[int, List[bool]] = {}
        values: Dict[int, Optional[int]] = {}
        co_values: List[int] = [0] * len(combine_only_sets)
        for gp, idx_list in by_gp.items():
            eng = get_engine_degraded(backend, mesh, gp)
            if columnar:
                v, vals, co = _verify_combine_columns(
                    gp,
                    eng,
                    [groups[gi] for gi in idx_list],
                    threshold,
                    combine_only_sets if gp == co_gp else (),
                )
                verdicts.update(zip(idx_list, v))
                values.update(zip(idx_list, vals))
                if gp == co_gp:
                    co_values = co
                continue
            # verification duals first (2 per share), then combine terms
            # (threshold per set) ride the same dispatch as u2^0 = 1
            # dummy-factor duals
            u1, e1, u2, e2 = _verify_dual_items(gp, groups, idx_list)
            n_dual = len(u1)
            comb_spans: List[tuple] = []  # (store(value), memo_key)

            def queue_combine(shares, store) -> None:
                """Memo-hit now or queue threshold Lagrange terms; the
                post-dispatch loop below routes the product to ``store``.
                One body for both the verified groups and the
                combine-only sets — they cannot drift."""
                use = sorted(shares, key=lambda s: s.index)[:threshold]
                xs = [s.index for s in use]
                if len(set(xs)) != len(xs):
                    raise ValueError("duplicate share indices")
                key = (gp, threshold, tuple((s.index, s.d) for s in use))
                hit = _COMBINE_MEMO.get(key)
                if hit is not None:
                    store(hit)
                    return
                lams = lagrange_coeff_at_zero(xs, gp.q)
                for sh, lam in zip(use, lams):
                    u1.append(sh.d % gp.p); e1.append(lam)
                    u2.append(1); e2.append(0)
                comb_spans.append((store, key))

            for gi in idx_list:
                pub, _base, shares, _context = groups[gi]
                if len(shares) < threshold:
                    values[gi] = None
                    continue
                queue_combine(
                    shares, lambda v, gi=gi: values.__setitem__(gi, v)
                )
            if gp == co_gp:  # equality, not identity: by_gp keys by value
                for ci, shares in enumerate(combine_only_sets):
                    if len(shares) < threshold:
                        raise ValueError(
                            f"need >= {threshold} shares, got {len(shares)}"
                        )
                    queue_combine(
                        shares, lambda v, ci=ci: co_values.__setitem__(ci, v)
                    )
            a = eng.dual_pow_batch(u1, e1, u2, e2)
            verdicts.update(_cp_verdicts(gp, groups, idx_list, a))
            off = n_dual
            for store, key in comb_spans:
                acc = 1
                for term in a[off : off + threshold]:
                    acc = acc * term % gp.p
                off += threshold
                if len(_COMBINE_MEMO) >= _COMBINE_MEMO_CAP:
                    _COMBINE_MEMO.clear()
                _COMBINE_MEMO[key] = acc
                store(acc)
        return (
            [verdicts[gi] for gi in range(len(groups))],
            [values[gi] for gi in range(len(groups))],
            co_values,
        )


def verify_shares(
    pub: ThresholdPublicKey,
    base: int,
    shares: Sequence[DhShare],
    context: bytes,
    backend: str = "cpu",
    mesh=None,
) -> List[bool]:
    """Single-group convenience over ``verify_share_groups``."""
    if not shares:
        return []
    return verify_share_groups(
        [(pub, base, shares, context)], backend, mesh
    )[0]


class SharePool:
    """Sender-keyed pool of DhShares with deferred batched verification.

    One slot per roster sender (an honest node submits exactly one
    share per context), so a Byzantine peer can only ever occupy — and
    then burn — its own slot: a sender whose share fails verification
    is remembered in ``_burned`` and can never resubmit, bounding both
    memory and re-verification work.  Valid shares are deduped by
    Shamir index before combination (a Byzantine sender may replay
    another node's valid share, which must not trip the distinct-
    index requirement of Lagrange interpolation).

    Shares sit in a *pending* set until verification verdicts arrive —
    either via ``try_verified`` (self-contained, one verify call per
    pool) or via ``collect_pending``/``apply_verdicts`` driven by the
    protocol hub, which verifies MANY pools' pending shares in one
    cross-instance dispatch (protocol.hub.CryptoHub).

    Shared by the BBA common coin and the TPKE decryption path — the
    two consumers of threshold shares in HBBFT.
    """

    __slots__ = ("threshold", "_pending", "_verified", "_burned",
                 "_seen", "_lazy", "_n", "_idx_cover", "_opt")

    def __init__(self, threshold: int):
        self.threshold = threshold
        self._pending: Dict[str, DhShare] = {}
        self._verified: Dict[str, DhShare] = {}
        self._burned: set = set()
        # one membership set over pending+verified+burned+lazy: the
        # add paths make a single probe instead of three
        self._seen: set = set()
        # lazily-parked (sender, index, d, e, z) rows: the live path's
        # wave handlers park ~N shares per pool but only ~threshold
        # ever get consumed — DhShare objects materialize on first
        # structured access, so arrival cost is probe+append
        self._lazy: List[tuple] = []
        self._n = 0  # pending+verified+lazy (burns decrement)
        # distinct Shamir indices held (pending+verified+lazy) — an
        # upper bound on achievable interpolation coverage, letting
        # lazy row-store pulls stop the moment the threshold is
        # coverable instead of materializing a whole wave (recomputed
        # exactly when a burn invalidates it)
        self._idx_cover: set = set()
        # optimistic_subset()'s answer for the pool as it stands (any
        # add or verdict drops it): the settler asks once to offer the
        # subset to the batched combine and once more to use it
        self._opt: Optional[List[DhShare]] = None

    def covered(self) -> int:
        return len(self._idx_cover)

    def add(self, sender: str, share: DhShare) -> bool:
        """First share per non-burned sender wins."""
        if sender in self._seen:
            return False
        self._seen.add(sender)
        self._pending[sender] = share
        self._idx_cover.add(share.index)
        self._n += 1
        self._opt = None
        return True

    def add_lazy(
        self, sender: str, index: int, d: int, e: int, z: int
    ) -> bool:
        """``add`` without constructing the DhShare: the batched wave
        handlers' per-share fast path."""
        if sender in self._seen:
            return False
        self._seen.add(sender)
        self._lazy.append((sender, index, d, e, z))
        self._idx_cover.add(index)
        self._n += 1
        self._opt = None
        return True

    def _materialize(self) -> None:
        if self._lazy:
            pending = self._pending
            for sender, index, d, e, z in self._lazy:
                pending[sender] = DhShare(index, d, e, z)
            self._lazy.clear()

    def __len__(self) -> int:
        """Potential size: pending + verified (the threshold trigger)."""
        return self._n

    def collect_pending(
        self, limit: Optional[int] = None
    ) -> Tuple[List[str], List[DhShare]]:
        """Unverified shares for an external batched verify.

        ``limit=None`` returns everything.  The hub passes
        ``need_more()`` instead: only enough pending shares to reach
        the threshold (counting distinct verified indices already
        held), sorted by sender for determinism.  Surplus shares stay
        parked — verifying a full wave's N shares when f+1 suffice is
        pure modexp waste (the round-3 wave-batching regression: ~2.7x
        the CP checks per pool); if a collected share fails, the next
        flush pulls replacements from the parked surplus.
        """
        self._materialize()
        if limit is None:
            senders = list(self._pending)
        else:
            # skip shares whose Shamir index is already covered (a
            # replayed honest share verifies fine but adds no distinct
            # index) — both against the verified set and within the
            # selected slice; skipped shares stay parked as fallback
            have = {s.index for s in self._verified.values()}
            senders = []
            for sender in sorted(self._pending):
                if len(senders) >= max(limit, 0):
                    break
                idx = self._pending[sender].index
                if idx in have:
                    continue
                have.add(idx)
                senders.append(sender)
        return senders, [self._pending[s] for s in senders]

    def need_more(self) -> int:
        """How many additional verified index-distinct shares the
        threshold still needs (0 = ready or no point verifying)."""
        have = len({s.index for s in self._verified.values()})
        return max(self.threshold - have, 0)

    def apply_verdicts(self, senders: Sequence[str], ok: Sequence[bool]) -> None:
        """Record external verification verdicts: valid shares move to
        the verified set, senders of invalid ones burn."""
        burned_any = False
        self._opt = None
        for sender, good in zip(senders, ok):
            share = self._pending.pop(sender, None)
            if share is None:
                continue
            if good:
                self._verified[sender] = share
            else:
                self._burned.add(sender)
                self._n -= 1
                burned_any = True
        if burned_any:
            # the burned share may have been an index's only holder:
            # recompute the coverage bound exactly (rare path)
            self._idx_cover = {
                s.index for s in self._pending.values()
            } | {s.index for s in self._verified.values()} | {
                row[1] for row in self._lazy
            }

    def ready(self) -> Optional[List[DhShare]]:
        """>= threshold index-distinct verified shares, or None."""
        by_index: Dict[int, DhShare] = {}
        for share in self._verified.values():
            by_index.setdefault(share.index, share)
        if len(by_index) < self.threshold:
            return None
        return list(by_index.values())

    def optimistic_subset(self) -> Optional[List[DhShare]]:
        """Threshold index-distinct shares counting UNVERIFIED ones
        (verified preferred, then pending by sender order), or None.

        For consumers whose combined output is self-authenticating
        (TPKE: the ciphertext tag checks the combined KEM value), an
        optimistic combine on this subset replaces per-share CP
        verification in the honest case entirely; a tag failure means
        some selected share was invalid, and the caller falls back to
        the verified path, which burns the culprit.  NOT safe for the
        common coin — its combined value has no independent check."""
        if self._opt is not None:
            return self._opt
        self._materialize()
        by_index: Dict[int, DhShare] = {}
        for share in self._verified.values():
            by_index.setdefault(share.index, share)
        for sender in sorted(self._pending):
            share = self._pending[sender]
            by_index.setdefault(share.index, share)
        if len(by_index) < self.threshold:
            return None
        self._opt = list(by_index.values())
        return self._opt

    def try_verified(self, verify_fn) -> Optional[List[DhShare]]:
        """Self-contained threshold check: if >= threshold shares are
        pooled, batch-verify the pending ones (``verify_fn(shares) ->
        List[bool]``, ONE dispatch under 'tpu'), burn invalid senders,
        and return >= threshold index-distinct valid shares — or None
        if not there yet."""
        if len(self) < self.threshold:
            return None
        senders, shares = self.collect_pending()
        if shares:
            self.apply_verdicts(senders, verify_fn(shares))
        return self.ready()


# The combined value is a pure function of (group, threshold, the
# chosen subset's (index, d) pairs) — z/e play no part in combining.
# Every node of a cluster combines the same subset for the same coin
# or ciphertext, so a bounded memo turns N identical ~threshold-sized
# exponentiation batches into one (cleared wholesale at the cap; keys
# carry the share values, so distinct inputs can never collide).
# Entries hold threshold-many group elements (KBs at large N), so the
# cap is deliberately small; a working set is ~2N live combines.
_COMBINE_MEMO: Dict[tuple, int] = {}
_COMBINE_MEMO_CAP = 1 << 12


def combine_shares(
    shares: Sequence[DhShare],
    threshold: int,
    group: GroupParams = DEFAULT_GROUP,
) -> int:
    """Lagrange-combine >= threshold verified shares into base^s."""
    xs, ds = _combine_subset(shares, threshold)
    key = (group, threshold, tuple(zip(xs, ds)))
    hit = _COMBINE_MEMO.get(key)
    if hit is not None:
        return hit
    lams = lagrange_coeff_at_zero(xs, group.q)
    acc = 1
    for term in host_pow_batch([d % group.p for d in ds], lams, group):
        acc = acc * term % group.p
    if len(_COMBINE_MEMO) >= _COMBINE_MEMO_CAP:
        _COMBINE_MEMO.clear()
    _COMBINE_MEMO[key] = acc
    return acc


# ---------------------------------------------------------------------------
# TPKE proper: hashed-ElGamal KEM over the threshold-DH core
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Ciphertext:
    c1: int  # g^r
    c2: bytes  # msg XOR keystream
    tag: bytes  # integrity tag binding (key, c1, c2)


def _keystream(key: bytes, length: int) -> bytes:
    n_blocks = (length + 31) // 32
    if n_blocks >= 16:
        # batch-size payloads (tens of KB per proposer): hash every
        # counter block in one native crossing — byte-identical to
        # the scalar loop below
        k = len(key)
        rows = np.empty((n_blocks, k + 6), dtype=np.uint8)
        rows[:, :k] = np.frombuffer(key, dtype=np.uint8)
        rows[:, k : k + 4] = (
            np.arange(n_blocks, dtype=">u4")
            .view(np.uint8)
            .reshape(n_blocks, 4)
        )
        rows[:, k + 4] = ord("k")
        rows[:, k + 5] = ord("s")
        return sha256_rows(rows).tobytes()[:length]
    out = []
    ctr = 0
    while 32 * len(out) < length:
        out.append(
            hashlib.sha256(key + ctr.to_bytes(4, "big") + b"ks").digest()
        )
        ctr += 1
    return b"".join(out)[:length]


def _xor_bytes(a: bytes, b: bytes) -> bytes:
    """a ^ b over equal-length byte strings, vectorized: the stream
    cipher runs over whole proposed batches (tens of KB per proposer),
    where a per-byte python loop costs more than the group math."""
    import numpy as np

    return (
        np.frombuffer(a, dtype=np.uint8) ^ np.frombuffer(b, dtype=np.uint8)
    ).tobytes()


class Tpke:
    """Threshold decryption service for one key set."""

    def __init__(
        self, pub: ThresholdPublicKey, backend: str = "cpu", mesh=None
    ):
        self.pub = pub
        self.backend = backend
        self.mesh = mesh
        self.group = pub.group  # the key set carries its group

    # TPKE.Encrypt (docs/THRESHOLD_ENCRYPTION-EN.md:34)
    def encrypt(self, msg: bytes, rng=secrets) -> Ciphertext:
        gp = self.group
        # 8 excess bytes: unbiased KEM exponent (same rule as
        # _shamir_shares / issue_share)
        r = (
            int.from_bytes(rng.token_bytes(gp.nbytes + 8), "big") % gp.q
        )
        c1, kem = host_pow_batch(
            [gp.g, self.pub.master], [r, r], gp
        )  # g^r, h^r
        key = hashlib.sha256(b"kem" + _ibytes(kem, gp.nbytes)).digest()
        c2 = _xor_bytes(msg, _keystream(key, len(msg)))
        tag = hmac.new(
            key, _ibytes(c1, gp.nbytes) + c2, hashlib.sha256
        ).digest()
        return Ciphertext(c1=c1, c2=c2, tag=tag)

    def context(self, ct: Ciphertext) -> bytes:
        """The CP-proof context binding shares to this ciphertext
        (public: the protocol hub groups cross-instance verifies by
        (pub, base, context))."""
        return (
            b"tpke|"
            + _ibytes(ct.c1, self.group.nbytes)
            + hashlib.sha256(ct.c2).digest()
        )

    _context = context  # internal alias

    # TPKE.DecShare (docs/THRESHOLD_ENCRYPTION-EN.md:35)
    def dec_share(
        self, share: ThresholdSecretShare, ct: Ciphertext
    ) -> DhShare:
        return issue_share(share, ct.c1, self._context(ct), self.group)

    def dec_share_items(
        self, share: ThresholdSecretShare, cts: Sequence[Ciphertext]
    ) -> List[tuple]:
        """The ``(share, base, context, vk)`` rows
        ``issue_shares_batch`` takes for this key set — the ONE place
        the CP-proof context/vk binding is built, shared by
        ``dec_share_batch`` and the CryptoHub's eager dec-share
        column (K-deep pipelining) so the two issue paths can never
        bind different contexts."""
        vk = self.pub.verification_keys[share.index - 1]
        return [(share, ct.c1, self._context(ct), vk) for ct in cts]

    def dec_share_batch(
        self, share: ThresholdSecretShare, cts: Sequence[Ciphertext]
    ) -> List[DhShare]:
        """All of an epoch's decryption shares in ONE batched
        exponentiation dispatch and one CP-nonce entropy draw —
        semantically ``[dec_share(share, ct) for ct in cts]`` (the
        wave-columnar protocol path's issue seam; scalar dec_share
        was N 4-exp calls + N urandom reads per node per epoch)."""
        if not cts:
            return []
        return issue_shares_batch(
            self.dec_share_items(share, cts),
            group=self.group,
            backend=self.backend,
            mesh=self.mesh,
        )

    def verify_dec_shares(
        self, ct: Ciphertext, shares: Sequence[DhShare]
    ) -> List[bool]:
        return verify_shares(
            self.pub, ct.c1, shares, self._context(ct), self.backend,
            self.mesh,
        )

    # TPKE.Decrypt (docs/THRESHOLD_ENCRYPTION-EN.md:36)
    def combine(
        self, ct: Ciphertext, shares: Sequence[DhShare]
    ) -> bytes:
        """Recover the plaintext from >= f+1 *verified* shares.

        Raises ValueError if the integrity tag does not check out —
        deterministically for every correct node, since the combined
        KEM value is independent of which valid share subset was used.
        """
        return self.open(
            ct, combine_shares(shares, self.pub.threshold, self.group)
        )

    def open(self, ct: Ciphertext, kem: int) -> bytes:
        """``combine`` from the combined KEM value on: tag check, then
        the plaintext — for callers whose combines ran batched
        (``combine_shares_batch``, the CryptoHub's combine column)."""
        key = hashlib.sha256(b"kem" + _ibytes(kem, self.group.nbytes)).digest()
        tag = hmac.new(
            key, _ibytes(ct.c1, self.group.nbytes) + ct.c2, hashlib.sha256
        ).digest()
        if not hmac.compare_digest(tag, ct.tag):
            raise ValueError("TPKE integrity check failed")
        return _xor_bytes(ct.c2, _keystream(key, len(ct.c2)))


__all__ = [
    "is_group_element",
    "group_members",
    "group_check_tally",
    "reset_group_check_tally",
    "ThresholdPublicKey",
    "ThresholdSecretShare",
    "DhShare",
    "SharePool",
    "Ciphertext",
    "deal",
    "issue_share",
    "issue_shares_batch",
    "issue_share_columns",
    "ShareColumns",
    "ShareWave",
    "share_tally",
    "reset_share_tally",
    "verify_shares",
    "verify_share_groups",
    "verify_and_combine_share_groups",
    "combine_shares",
    "combine_shares_batch",
    "combine_share_wave",
    "lagrange_coeff_at_zero",
    "hash_to_group",
    "Tpke",
]
