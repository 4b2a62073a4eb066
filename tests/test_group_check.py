"""The subgroup check (``ops.tpke.group_members`` / ``is_group_element``):
in a safe-prime group, OpenSSL's Kronecker symbol (x/p) == 1
(native/modpow_wide.cpp ``kronecker_wide_batch`` through
``ops.widepow.kronecker_rows``); in any other group, or without the
kernel, x^q == 1 (mod p).  Both must give today's answer on every
value: 0, 1, 2, g, p - 1, p, p + 1, 2p, squares, non-residues and
seeded random values, in the 256-bit default group, GROUP384 and
RFC 3526's MODP-14."""

from __future__ import annotations

import random
import struct

import pytest

from cleisthenes_tpu.ops import modmath as mm
from cleisthenes_tpu.ops import tpke, widepow
from cleisthenes_tpu.protocol.honeybadger import deserialize_ciphertext

GROUPS = {
    "default": mm.DEFAULT_GROUP,
    "group384": mm.GROUP384,
    "modp14": mm.GROUP14,
}
RANDOM_ROWS = 300


def _values(group: mm.GroupParams, seed: int, rows: int = RANDOM_ROWS):
    """The edge values, 8 squares, 8 non-residues (-1 times a square:
    p = 3 mod 4) and ``rows`` seeded random values below 2p."""
    p = group.p
    rnd = random.Random(seed)
    squares = [pow(rnd.randrange(2, p), 2, p) for _ in range(8)]
    edges = [0, 1, 2, group.g, p - 1, p, p + 1, 2 * p]
    return (
        edges + squares + [p - s for s in squares]
        + [rnd.randrange(2 * p) for _ in range(rows)]
    )


def _want(xs, group: mm.GroupParams):
    """Today's test: 1 < x < p and x^q == 1 (mod p)."""
    inside = [x for x in xs if 1 < x < group.p]
    powers = iter(mm.host_pow_batch(inside, [group.q] * len(inside), group))
    return [1 < x < group.p and next(powers) == 1 for x in xs]


@pytest.fixture
def tally():
    tpke.reset_group_check_tally()
    yield tpke.group_check_tally
    tpke.reset_group_check_tally()


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_symbol_path_matches_the_exponentiation(name, tally):
    group = GROUPS[name]
    xs = _values(group, seed=4401)
    assert tpke.group_members(xs, group) == _want(xs, group)
    assert tally() == {
        "calls": 1, "rows": len(xs), "kronecker": len(xs), "pow": 0
    }


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_edges_and_residues(name, tally):
    """The named rejections: 0, the identity, the order-2 element, p and
    past it, every non-residue; every square above 1 admitted."""
    group = GROUPS[name]
    p = group.p
    for x in (0, 1, p - 1, p, p + 1, 2 * p, p - group.g):
        assert not tpke.is_group_element(x, group)
    assert tpke.is_group_element(group.g, group)
    rnd = random.Random(4402)
    for _ in range(8):
        s = pow(rnd.randrange(2, p - 1), 2, p)
        assert tpke.is_group_element(s, group) == (s != 1)
        assert not tpke.is_group_element(p - s, group)
    assert tally()["pow"] == 0 and tally()["calls"] == 7 + 1 + 16


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_kronecker_rows_is_the_legendre_symbol(name):
    """The kernel's symbol itself: 0 at 0, 1 on squares, -1 on their
    negatives, and Euler's criterion on random values."""
    group = GROUPS[name]
    p = group.p
    rnd = random.Random(4403)
    squares = [pow(rnd.randrange(2, p - 1), 2, p) for _ in range(16)]
    xs = [rnd.randrange(1, p) for _ in range(32)]
    assert widepow.kronecker_rows([0] + squares, p) == [0] + [1] * 16
    assert widepow.kronecker_rows([p - s for s in squares], p) == [-1] * 16
    euler = [1 if pow(x, group.q, p) == 1 else -1 for x in xs]
    assert widepow.kronecker_rows(xs, p) == euler
    assert widepow.kronecker_rows([], p) == []


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_without_the_kernel_the_pow_path_answers_alike(
    name, tally, monkeypatch
):
    """A loader that finds no library: the symbol is None and every
    check takes x^q, with the same answers."""
    group = GROUPS[name]
    rows = 8 if group.p.bit_length() > 1024 else 64  # pow() at 2048 bits
    xs = _values(group, seed=4404, rows=rows)
    want = _want(xs, group)
    monkeypatch.setattr(widepow, "load_modpow_wide", lambda: None)
    assert widepow.kronecker_rows(xs[:4], group.p) is None
    assert tpke.group_members(xs, group) == want
    assert tally() == {
        "calls": 1, "rows": len(xs), "kronecker": 0, "pow": len(xs)
    }


@pytest.mark.parametrize(
    "p",
    [
        mm.P * 5,  # odd and composite: p = 2q + 1 with p not prime
        mm.P + 4,  # odd and a multiple of 3 (a safe prime is 2 mod 3)
    ],
    ids=["5p", "p_plus_4"],
)
def test_composite_modulus_takes_the_pow_path(p, tally):
    """p = 2q + 1 holds but p is not prime: the symbol is not Euler's
    criterion there, so the check is x^q itself."""
    assert p % 3 == 0 or p % 5 == 0
    group = mm.GroupParams(p=p, q=(p - 1) // 2, g=4)
    assert not tpke._symbol_decides(group)
    xs = random.Random(4405).sample(range(2, 1 << 20), 48)
    xs += [pow(x, 2, p) for x in xs[:8]] + [0, 1, p - 1, p]
    want = [1 < x < p and pow(x, group.q, p) == 1 for x in xs]
    assert tpke.group_members(xs, group) == want
    assert tally()["pow"] == len(xs) and tally()["kronecker"] == 0


def test_group_not_safe_prime_shaped_takes_the_pow_path(tally):
    """q is not (p - 1) / 2 (here the order of a smaller subgroup is
    claimed): the check keeps today's x^q == 1."""
    p = mm.P
    group = mm.GroupParams(p=p, q=3, g=4)
    assert not tpke._symbol_decides(group)
    rnd = random.Random(4406)
    xs = [0, 1, 2, 4, p - 1] + [rnd.randrange(2, p) for _ in range(32)]
    want = [1 < x < p and pow(x, 3, p) == 1 for x in xs]
    assert tpke.group_members(xs, group) == want
    assert tally() == {
        "calls": 1, "rows": len(xs), "kronecker": 0, "pow": len(xs)
    }


def test_tally_counts_calls_and_rows_by_path(tally, monkeypatch):
    group = mm.DEFAULT_GROUP
    tpke.group_members([2, 3, 4], group)
    assert tpke.is_group_element(group.g, group)
    monkeypatch.setattr(widepow, "load_modpow_wide", lambda: None)
    tpke.group_members([5, 6], group)
    assert tpke.group_members([], group) == []
    assert tally() == {"calls": 4, "rows": 6, "kronecker": 4, "pow": 2}
    tpke.reset_group_check_tally()
    assert tally() == {"calls": 0, "rows": 0, "kronecker": 0, "pow": 0}


def _frame(c1: int, group: mm.GroupParams) -> bytes:
    body = b"payload under test"
    return (
        c1.to_bytes(group.nbytes, "big")
        + struct.pack(">I", len(body)) + body + bytes(32)
    )


@pytest.mark.parametrize(
    "bad", ["order_two", "non_residue", "zero", "identity", "p"]
)
def test_modp14_ciphertext_with_bad_c1_is_refused(bad, tally):
    group = mm.GROUP14
    p = group.p
    good = pow(group.g, 0x1234567, p)
    c1 = {
        "order_two": p - 1,
        "non_residue": p - good,
        "zero": 0,
        "identity": 1,
        "p": p,
    }[bad]
    assert deserialize_ciphertext(_frame(good, group), group).c1 == good
    with pytest.raises(ValueError, match="prime-order subgroup"):
        deserialize_ciphertext(_frame(c1, group), group)
    assert tally()["kronecker"] == 2 and tally()["pow"] == 0
