"""The plain reference for exponentiations in RFC 3526's 2048-bit MODP
group 14, the group a deployment names by ``crypto_group = "modp14"``.
It imports nothing of the program: the group is written out from the RFC,
and every result is Python's own ``pow()``.  ``tools/modexpbench.py``
and the tests compare the program's device kernels and host kernel with
it, byte for byte.

RFC 3526, section 3 ("2048-bit MODP Group", id 14): the prime is
2^2048 - 2^1984 - 1 + 2^64 * ( [2^1918 pi] + 124476 ), written out below
in the RFC's hexadecimal; the generator is 2.  p = 2q + 1 with q prime,
and since p = 7 (mod 8), 2 is a quadratic residue: it generates the
subgroup of order q in which the threshold schemes work.  ``check_group``
verifies all of it (Miller-Rabin on p and q; the order of g).

What a share check evaluates (the Chaum-Pedersen proof that a share
d = base^s was made with the secret s behind the verification key
h = g^s): with the proof's challenge e and response z,

    A1 = g^z * h^(-e)      A2 = base^z * d^(-e)      (mod p)

and the share is accepted when the challenge recomputed from the
transcript (context, base, h, d, A1, A2), hashed and reduced mod q,
equals e.  Each of A1 and A2 is one ``dual_pow`` item: u1^e1 * u2^e2
with u1 = g or base, e1 = z, u2 = h or d, e2 = q - e.  Issuing a share
is one ``pow`` item a value (base^s, and the proof's g^w and base^w).
"""

from __future__ import annotations

import random
from typing import List, Sequence

P = int(
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
Q = (P - 1) // 2
G = 2
NBYTES = 256  # a group element, big-endian


def probably_prime(n: int, rounds: int = 16, seed: int = 3526) -> bool:
    """Miller-Rabin with ``rounds`` seeded random bases."""
    if n < 4:
        return n in (2, 3)
    if n % 2 == 0:
        return False
    d, s = n - 1, 0
    while d % 2 == 0:
        d, s = d // 2, s + 1
    rnd = random.Random(seed)
    for _ in range(rounds):
        x = pow(rnd.randrange(2, n - 1), d, n)
        if x in (1, n - 1):
            continue
        for _ in range(s - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def check_group() -> None:
    """Raise unless p and q are prime, p = 2q + 1, and g has order q."""
    assert P.bit_length() == 2048 and P == 2 * Q + 1
    assert probably_prime(P) and probably_prime(Q), "not a safe prime"
    assert P % 8 == 7 and pow(G, Q, P) == 1 and G != 1, "g not of order q"


def pow_rows(bases: Sequence[int], exps: Sequence[int]) -> List[int]:
    """[b^e mod p]."""
    return [pow(b, e, P) for b, e in zip(bases, exps)]


def dual_pow_rows(
    u1: Sequence[int], e1: Sequence[int], u2: Sequence[int], e2: Sequence[int]
) -> List[int]:
    """[u1^e1 * u2^e2 mod p]."""
    return [
        pow(a, x, P) * pow(b, y, P) % P for a, x, b, y in zip(u1, e1, u2, e2)
    ]


def to_rows(values: Sequence[int]) -> bytes:
    """The values as NBYTES-byte big-endian rows, one after the other:
    what 'byte for byte' compares."""
    return b"".join(v.to_bytes(NBYTES, "big") for v in values)


__all__ = ["P", "Q", "G", "NBYTES", "check_group", "probably_prime",
           "pow_rows", "dual_pow_rows", "to_rows"]
