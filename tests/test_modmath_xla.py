"""The XLA modexp kernels and batched TPKE helpers — pure JAX, no
native toolchain required (deliberately NOT in test_native.py, whose
module-level skip would hide kernel regressions on toolchain-less
hosts)."""

import random

import numpy as np
import pytest

from cleisthenes_tpu.ops import modmath as mm
from cleisthenes_tpu.ops import tpke as T


def test_xla_pow_path_above_host_floor():
    """The transposed-layout (NLIMBS, B) kernel itself: ModEngine
    delegates sub-floor batches to the host, so pin the batch AT the
    floor (strict `<` comparison) to hold the device path covered
    while landing exactly on the 8192 compile bucket."""
    eng = mm.ModEngine("tpu", group=mm.DEFAULT_GROUP)
    B = eng.HOST_FLOOR
    rnd = random.Random(7)
    p = mm.DEFAULT_GROUP.p
    bases = [rnd.randrange(1, p) for _ in range(B)]
    exps = [rnd.randrange(0, p) for _ in range(B)]
    got = eng.pow_batch(bases, exps)
    # spot-check a deterministic sample (full python-pow comparison at
    # 8k items costs more than the kernel run)
    for i in range(0, B, 997):
        assert got[i] == pow(bases[i], exps[i], p)
    u2 = list(reversed(bases))
    e2 = list(reversed(exps))
    dual = eng.dual_pow_batch(bases, exps, u2, e2)
    for i in range(0, B, 997):
        assert dual[i] == pow(bases[i], exps[i], p) * pow(u2[i], e2[i], p) % p


def test_mont_mul_batch_layout_roundtrip():
    """mont_mul_batch keeps its (B, NLIMBS) public surface over the
    transposed kernel."""
    import numpy as np

    rnd = random.Random(3)
    p = mm.DEFAULT_GROUP.p
    xs = [rnd.randrange(1, p) for _ in range(8)]
    ys = [rnd.randrange(1, p) for _ in range(8)]
    a = np.stack([mm.int_to_limbs(x) for x in xs])
    b = np.stack([mm.int_to_limbs(y) for y in ys])
    out = np.asarray(mm.mont_mul_batch(a, b))
    r_inv = pow(mm.R, -1, p)
    for i in range(8):
        assert mm.limbs_to_int(out[i]) == xs[i] * ys[i] * r_inv % p


def test_issue_and_combine_batch_match_scalar():
    """issue_shares_batch / combine_shares_batch vs their scalar
    equivalents (ops/tpke.py)."""
    pub, shares = T.deal(4, 2, seed=5)
    base = pow(T.DEFAULT_GROUP.g, 12345, T.DEFAULT_GROUP.p)
    ctx = b"batch-issue-test"
    vks = pub.verification_keys
    items = [(s, base, ctx, vks[s.index - 1]) for s in shares]
    out = T.issue_shares_batch(items)
    assert [s.index for s in out] == [s.index for s in shares]
    # every batched share verifies under the scalar verifier
    assert all(T.verify_shares(pub, base, out, ctx))
    # vk=None recomputes the verification key: same validity
    out2 = T.issue_shares_batch([(shares[0], base, ctx, None)])
    assert all(T.verify_shares(pub, base, out2, ctx))
    # combines (scalar vs batch vs distinct subsets) agree
    a = T.combine_shares(out[:2], 2)
    b = T.combine_shares(out[2:4], 2)
    assert a == b  # subset independence
    got = T.combine_shares_batch([out[:2], out[1:3], out[2:]], 2)
    assert got == [a, a, a]


def test_pow_batch_grouped_device_path_with_splits_and_tails():
    """The comb kernel's full engine path — G_ROW splitting, per-size
    compile buckets, and strictly-ordered reassembly of a group whose
    tail slice lands in a different bucket — above the device
    crossover (every other suite runs backend='cpu' and would take the
    flat fallback, leaving this logic untested)."""
    eng = mm.ModEngine("tpu", group=mm.DEFAULT_GROUP)
    rnd = random.Random(11)
    p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
    groups = [
        (rnd.randrange(2, p), [rnd.randrange(0, q) for _ in range(sz)])
        # 700/1200 force G_ROW=512 splits with odd tails; 3 keeps a
        # tiny group in the same dispatch plan; total 2003 >= crossover
        for sz in (700, 1200, 100, 3)
    ]
    out = eng.pow_batch_grouped(groups)
    for (base, exps), res in zip(groups, out):
        assert len(res) == len(exps)
        for i in range(0, len(exps), 97):
            assert res[i] == pow(base, exps[i], p)
        assert res[-1] == pow(base, exps[-1], p)  # tail ordering


# ---------------------------------------------------------------------------
# the byte-column entry points: the int ones' results, floors, programs
# ---------------------------------------------------------------------------

# the sizes of test_pow_batch_grouped_device_path_with_splits_and_tails
# (its four compiled comb shapes are reused): G_ROW splits with odd
# tails, a padded bucket, a tiny group
_GROUP_SIZES = (700, 1200, 100, 3)


def _grouped_inputs(seed):
    rnd = random.Random(seed)
    p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
    return [
        (rnd.randrange(2, p), [rnd.randrange(0, q) for _ in range(sz)])
        for sz in _GROUP_SIZES
    ]


def _as_blocks(groups):
    return [([base], mm.exps_to_bytes(exps)[None]) for base, exps in groups]


def _engine_case(case, monkeypatch):
    if case == "cpu":
        return mm.ModEngine("cpu")
    if case == "cpu_no_native":
        eng = mm.ModEngine("cpu")
        eng._nat = None  # python pow(), as without a toolchain
        return eng
    if case == "tpu_under_floor":
        # 2003 exponentiations: over the comb's floor only with a
        # native host kernel to beat, so pin the floor above them
        monkeypatch.setattr(mm.ModEngine, "HOST_FLOOR", 6 * 4096)
        return mm.ModEngine("tpu")
    assert case == "tpu_device"
    monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
    return mm.ModEngine("tpu")


@pytest.mark.parametrize(
    "case", ["cpu", "cpu_no_native", "tpu_under_floor", "tpu_device"]
)
def test_column_entry_points_match_int_entry_points(case, monkeypatch):
    """pow / dual_pow / grouped on byte columns give the int entry
    points' values on every backend: the native host kernel, python
    pow(), the 'tpu' engine under its floors and the XLA kernels —
    across the G_ROW split, odd tails and padded buckets."""
    eng = _engine_case(case, monkeypatch)
    rnd = random.Random(23)
    p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
    n = 11  # pads to the 16 bucket on the device
    u1 = [rnd.randrange(1, p) for _ in range(n)]
    u2 = [rnd.randrange(1, p) for _ in range(n)]
    e1 = [rnd.randrange(0, q) for _ in range(n)]
    e2 = [0, 1] + [rnd.randrange(0, q) for _ in range(n - 2)]
    cols = (
        mm.ints_to_bytes33(u1), mm.exps_to_bytes(e1),
        mm.ints_to_bytes33(u2), mm.exps_to_bytes(e2),
    )
    assert mm.bytes33_to_ints(eng.pow_cols(cols[0], cols[1])) == (
        eng.pow_batch(u1, e1)
    )
    assert mm.bytes33_to_ints(eng.dual_pow_cols(*cols)) == (
        eng.dual_pow_batch(u1, e1, u2, e2)
    )
    groups = _grouped_inputs(29)
    want = eng.pow_batch_grouped(groups)
    got = eng.pow_grouped_cols(_as_blocks(groups))
    assert [mm.bytes33_to_ints(g[0]) for g in got] == want
    for (base, exps), res in zip(groups, want):  # and both are right
        assert res[-1] == pow(base, exps[-1], p)
    # a block of several rows is that many groups of one size
    b3, e3 = groups[2]
    b4 = rnd.randrange(2, p)
    two = eng.pow_grouped_cols(
        [([b3, b4], np.stack([mm.exps_to_bytes(e3)] * 2))] + _as_blocks(
            [groups[0], groups[1], groups[3]]
        )
    )[0]
    assert mm.bytes33_to_ints(two[0]) == want[2]
    assert mm.bytes33_to_ints(two[1])[::37] == [
        pow(b4, e, p) for e in e3[::37]
    ]
    assert eng.pow_grouped_cols([]) == []
    assert eng.pow_cols(cols[0][:0], cols[1][:0]).shape == (0, 33)


@pytest.mark.parametrize("side", ["device", "host"])
def test_column_entry_points_add_no_program_and_tally_alike(
    side, monkeypatch
):
    """After the int call at a shape, the column call at that shape
    compiles nothing and tallies the same families, calls and items:
    the warm-up through the int entry points covers the column path,
    and the roofline's work count cannot tell the two apart."""
    from cleisthenes_tpu.ops import placement

    if side == "device":
        monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
    else:
        monkeypatch.setattr(mm.ModEngine, "HOST_FLOOR", 6 * 4096)
    eng = mm.ModEngine("tpu")
    groups = _grouped_inputs(31)
    rnd = random.Random(37)
    p, q = mm.DEFAULT_GROUP.p, mm.DEFAULT_GROUP.q
    u = [rnd.randrange(1, p) for _ in range(11)]
    e = [rnd.randrange(0, q) for _ in range(11)]
    ub, eb = mm.ints_to_bytes33(u), mm.exps_to_bytes(e)

    def int_calls():
        eng.pow_batch_grouped(groups)
        eng.pow_batch(u, e)
        eng.dual_pow_batch(u, e, u, e)

    def col_calls():
        eng.pow_grouped_cols(_as_blocks(groups))
        eng.pow_cols(ub, eb)
        eng.dual_pow_cols(ub, eb, ub, eb)

    placement.reset()
    int_calls()
    by_ints = placement.snapshot()
    programs = (mm._pow_fused_grouped, mm._dual_pow_fused, mm._pow_fused)
    compiled = [f._cache_size() for f in programs]
    placement.reset()
    col_calls()
    assert placement.snapshot() == by_ints
    assert [f._cache_size() for f in programs] == compiled
    fam = by_ints["modexp_12x22.comb"]
    calls = "device_calls" if side == "device" else "host_calls"
    assert fam[calls] > 0 and fam[calls.replace("calls", "items")] == sum(
        _GROUP_SIZES
    )


# ---------------------------------------------------------------------------
# the scalar field on byte rows (a CP proof's arithmetic mod q)
# ---------------------------------------------------------------------------


def _edge_rows(width, modulus, rnd, m=64):
    rows = np.frombuffer(
        rnd.randbytes(m * width), dtype=np.uint8
    ).reshape(m, width).copy()
    rows[0] = 0
    rows[1] = 255  # 2^(8 width) - 1
    top = 1 << (8 * width)
    for i, x in enumerate((modulus - 1, modulus, modulus + 1, 2 * modulus)):
        if x < top:
            rows[2 + i] = np.frombuffer(
                x.to_bytes(width, "big"), dtype=np.uint8
            )
    return rows


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize(
    "modulus", [mm.Q, mm.P, 1000003, 3, 1 << 200, mm.GROUP384.q],
    ids=["q", "p", "small", "three", "even", "wide"],
)
def test_scalar_rows_match_python_ints(modulus, native, monkeypatch):
    """mod_rows / mul_add_mod_rows are ``%`` and ``(a*b+c) %`` row by
    row, for every width up to the nonce's 64 bytes, at the edges of
    the modulus, with the native kernel and without it, and for moduli
    the kernel does not take (even, wider than 256 bits)."""
    from cleisthenes_tpu.ops.hashrows import be_rows_to_ints

    if native:
        if mm._native_modpow() is None:
            pytest.skip("no native toolchain")
    else:
        monkeypatch.setattr(mm, "_native_modpow", lambda: None)
    rnd = random.Random(41)
    if modulus.bit_length() > 256:
        # a residue would not fit the 32-byte rows: refused, loudly
        rows = _edge_rows(48, modulus, rnd)
        with pytest.raises(ValueError):
            mm.mod_rows(rows, modulus)
        with pytest.raises(ValueError):
            mm.mul_add_mod_rows(rows[:, :32], rows[:, :32], rows[:, :32], modulus)
        return
    for width in (1, 31, 32, 33, 40, 64, 72):
        rows = _edge_rows(width, modulus, rnd)
        got = mm.mod_rows(rows, modulus)
        assert got.shape == (len(rows), 32)
        assert be_rows_to_ints(got) == [
            x % modulus for x in be_rows_to_ints(rows)
        ]
    a, b, c = (_edge_rows(32, modulus, rnd) for _ in range(3))
    a[1], b[1], c[1] = 255, 255, 255
    got = mm.mul_add_mod_rows(a, b, c, modulus)
    assert be_rows_to_ints(got) == [
        (x * y + z) % modulus
        for x, y, z in zip(*(be_rows_to_ints(col) for col in (a, b, c)))
    ]
    assert mm.mod_rows(a[:0], modulus).shape == (0, 32)
    assert mm.mul_add_mod_rows(a[:0], b[:0], c[:0], modulus).shape == (0, 32)
