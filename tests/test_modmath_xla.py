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


def _below_p_all_ones() -> int:
    """The largest value under p whose limbs below the top one are all
    0xFFF."""
    top = mm.LIMB_BITS * (mm.NLIMBS - 1)
    x = (((mm.P >> top) - 1) << top) | ((1 << top) - 1)
    assert x < mm.P and all(l == mm.LIMB_MASK for l in mm.int_to_limbs(x)[:-1])
    return x


@pytest.mark.parametrize("width", [1, 8, 127, 128, 129, 1024])
def test_mont_mul_matches_python_ints(width):
    """The limb-plane product against Python integers: random operands
    and the edges, at widths on both sides of the in-program padding
    to whole rows of 128."""
    p = mm.P
    rnd = random.Random(width)
    edges = [0, 1, p - 1, p - 2, _below_p_all_ones()]
    xs = [edges[i % 5] if i < 10 else rnd.randrange(p) for i in range(width)]
    ys = [
        edges[(i // 5) % 5] if i < 25 else rnd.randrange(p)
        for i in range(width)
    ]
    if width >= 8:  # every edge against every edge, and against random
        xs[-5:] = edges
        ys[-5:] = [p - 1] * 5
    out = np.asarray(
        mm.mont_mul_batch(mm.ints_to_limbs(xs), mm.ints_to_limbs(ys))
    )
    assert out.shape == (width, mm.NLIMBS)
    assert (out >= 0).all() and (out <= mm.LIMB_MASK).all()
    r_inv = pow(mm.R, -1, p)
    assert mm.limbs_to_ints(out) == [
        x * y * r_inv % p for x, y in zip(xs, ys)
    ]


def test_lazy_carry_accumulators_stay_under_int32():
    """The worst case of the lazy carries, a = b = p - 1 (and, beyond
    what the product is ever given, every limb 0xFFF): an int64 replay
    of the limb steps never reaches 2^31, and the int32 kernel agrees
    with it."""
    p = mm.P
    m = mm.int_to_limbs(p).astype(np.int64)
    m_prime = (-pow(p, -1, 1 << mm.LIMB_BITS)) % (1 << mm.LIMB_BITS)

    def replay(x):
        a = mm.int_to_limbs(x).astype(np.int64)
        t = np.zeros(mm.NLIMBS, dtype=np.int64)
        peak = 0
        for i in range(mm.NLIMBS):
            t = t + a[i] * a
            q = ((t[0] & mm.LIMB_MASK) * m_prime) & mm.LIMB_MASK
            t = t + q * m
            peak = max(peak, int(t.max()))
            t = np.concatenate([[t[1] + (t[0] >> mm.LIMB_BITS)], t[2:], [0]])
            peak = max(peak, int(t.max()))
        return peak, sum(int(v) << (mm.LIMB_BITS * j) for j, v in enumerate(t))

    for x in (p - 1, mm.R - 1):
        peak, t = replay(x)
        assert peak < 1 << 31
        assert t < 2 * p or x >= p
    _peak, t = replay(p - 1)
    got = np.asarray(
        mm.mont_mul_batch(mm.ints_to_limbs([p - 1]), mm.ints_to_limbs([p - 1]))
    )
    assert mm.limbs_to_int(got[0]) == t % p == pow(p - 1, 2, p) * pow(
        mm.R, -1, p
    ) % p


def _random_cols(rnd, n):
    p = mm.P
    vals = [0, 1, p - 1, p - 2][: min(4, n)] + [
        rnd.randrange(p) for _ in range(max(0, n - 4))
    ]
    rnd.shuffle(vals)
    exps = np.frombuffer(rnd.randbytes(n * 32), dtype=np.uint8).reshape(n, 32)
    exps = exps.copy()
    exps[0] = 0
    exps[-1] = 255
    return mm.ints_to_bytes33(vals), exps


@pytest.mark.parametrize("rows", [11, 128, 300])
def test_device_columns_equal_the_native_host_kernel(rows, monkeypatch):
    """pow_cols and dual_pow_cols on the device programs
    (host_delegation off) against the native host kernel, byte for
    byte: a bucket under one row of 128, one row exactly, and a
    padded bucket of several rows."""
    if mm._native_modpow() is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
    dev, host = mm.ModEngine("tpu"), mm.ModEngine("cpu")
    rnd = random.Random(rows)
    u1, e1 = _random_cols(rnd, rows)
    u2, e2 = _random_cols(rnd, rows)
    assert (dev.pow_cols(u1, e1) == host.pow_cols(u1, e1)).all()
    assert (
        dev.dual_pow_cols(u1, e1, u2, e2) == host.dual_pow_cols(u1, e1, u2, e2)
    ).all()


@pytest.mark.parametrize(
    "heights,widths",
    [((3,), (40,)), ((1, 2), (600, 130)), ((9,), (128,))],
    ids=["one-bucket", "row-split-and-tail", "nine-rows"],
)
def test_device_comb_equals_the_native_host_kernel(
    heights, widths, monkeypatch
):
    """pow_grouped_cols on the comb program against the native host
    kernel, byte for byte: a single padded bucket; a group over G_ROW
    whose tail lands in another bucket beside a block of two rows; a
    block of nine rows (padded to sixteen bases)."""
    if mm._native_modpow() is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
    dev, host = mm.ModEngine("tpu"), mm.ModEngine("cpu")
    rnd = random.Random(sum(widths))
    blocks = []
    for h, w in zip(heights, widths):
        bases = [0, 1, mm.P - 1][:h] + [
            rnd.randrange(2, mm.P) for _ in range(max(0, h - 3))
        ]
        exps = np.frombuffer(
            rnd.randbytes(h * w * 32), dtype=np.uint8
        ).reshape(h, w, 32).copy()
        exps[0, 0] = 0
        exps[-1, -1] = 255
        blocks.append((bases, exps))
    got = dev.pow_grouped_cols(blocks)
    want = host.pow_grouped_cols(blocks)
    assert [g.shape for g in got] == [w.shape for w in want]
    for g, w in zip(got, want):
        assert (g == w).all()


# ---------------------------------------------------------------------------
# the compiled programs' structure: what the product must not grow back
# ---------------------------------------------------------------------------


def _computations(hlo: str):
    """name -> body text of every computation of an HLO module."""
    import re

    out = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \()", hlo):
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", block)
        if m:
            out[m.group(1)] = block
    return out


def _loop_bodies(hlo: str):
    """The text of every ``while`` body, with the computations it
    calls (fusions, calls) appended."""
    import re

    comps = _computations(hlo)
    bodies = []
    for name in re.findall(r"body=%?([\w.\-]+)", hlo):
        text, seen, todo = "", set(), [name]
        while todo:
            n = todo.pop()
            if n in seen or n not in comps:
                continue
            seen.add(n)
            text += comps[n]
            todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", comps[n])
        bodies.append(text)
    return bodies


def _spec_arrays():
    import jax.numpy as jnp

    m_limbs, m_prime, r_limbs, r2_limbs = mm._spec256(mm.DEFAULT_GROUP)
    return (
        jnp.asarray(m_limbs), jnp.int32(m_prime),
        jnp.asarray(r_limbs), jnp.asarray(r2_limbs),
    )


def _program_args(program, shape):
    u8 = np.uint8
    if program == "comb":
        nb, g = shape
        return mm._pow_fused_grouped, (
            np.zeros((nb, 33), u8), np.zeros((nb, g, 32), u8)
        )
    val, exp = np.zeros((shape, 33), u8), np.zeros((shape, 32), u8)
    if program == "pow":
        return mm._pow_fused, (val, exp)
    return mm._dual_pow_fused, (val, exp, val, exp)


@pytest.mark.parametrize(
    "program,shape", [("dual_pow", 8), ("pow", 8), ("comb", (8, 8))]
)
def test_compiled_programs_hold_no_loop_or_scatter_in_a_product(
    program, shape
):
    """The ladders compile to exactly one ``while`` (the bit ladder),
    the comb to its three scans, none nested in another; no loop body
    holds a ``dynamic-update-slice`` but the comb's own stacking of
    its two table scans' outputs: a product is straight-line code, and
    no limb loop, dynamic slice or row scatter grows back into it."""
    fn, args = _program_args(program, shape)
    hlo = fn.lower(*args, *_spec_arrays()).compile().as_text()
    bodies = _loop_bodies(hlo)
    assert len(bodies) == (3 if program == "comb" else 1)
    assert hlo.count(" while(") == len(bodies)
    for body in bodies:
        assert " while(" not in body
    scatters = sum(body.count("dynamic-update-slice(") for body in bodies)
    assert scatters == (2 if program == "comb" else 0)
    assert "scatter(" not in hlo


@pytest.mark.parametrize(
    "program,shape", [("dual_pow", 16384), ("comb", (64, 256))]
)
def test_sharded_programs_hold_no_collective(program, shape):
    """On four (virtual) devices the programs of the mesh cell's
    shapes partition with no collective: every device runs its own
    rows from unpack to repack."""
    import jax

    from cleisthenes_tpu.parallel.mesh import CryptoMesh

    mesh = CryptoMesh((2, 2))
    fn, args = _program_args(program, shape)
    shapes = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=mesh.spec_flat(a.ndim))
        for a in args
    ]
    hlo = (
        fn.lower(*shapes, *_spec_arrays(), mesh=mesh.mesh).compile().as_text()
    )
    for collective in (
        "all-gather", "all-to-all", "all-reduce", "collective-permute"
    ):
        assert collective + "(" not in hlo
        assert collective + "-start(" not in hlo


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
