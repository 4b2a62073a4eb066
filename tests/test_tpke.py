"""Threshold encryption, common coin, and the Montgomery mod-engine.

Covers the TPKE.SetUp/Encrypt/DecShare/Decrypt API matrix
(reference docs/THRESHOLD_ENCRYPTION-EN.md:33-36), Byzantine-share
rejection, and coin agreement/unpredictability properties
(docs/BBA-EN.md:163-181), on both backends.
"""

import random

import pytest

from cleisthenes_tpu.ops import coin as coin_mod
from cleisthenes_tpu.ops import modmath as mm
from cleisthenes_tpu.ops import tpke

rng = random.Random(99)


class TestModEngine:
    def test_pow_batch_tpu_matches_pow(self):
        eng = mm.ModEngine("tpu")
        bases = [rng.randrange(2, mm.P) for _ in range(9)]
        exps = [rng.randrange(mm.Q) for _ in range(9)]
        assert eng.pow_batch(bases, exps) == [
            pow(b, e, mm.P) for b, e in zip(bases, exps)
        ]

    def test_dual_pow_batch_tpu(self):
        eng = mm.ModEngine("tpu")
        u1 = [rng.randrange(2, mm.P) for _ in range(5)]
        u2 = [rng.randrange(2, mm.P) for _ in range(5)]
        e1 = [rng.randrange(mm.Q) for _ in range(5)]
        e2 = [rng.randrange(mm.Q) for _ in range(5)]
        assert eng.dual_pow_batch(u1, e1, u2, e2) == [
            pow(a, x, mm.P) * pow(b, y, mm.P) % mm.P
            for a, x, b, y in zip(u1, e1, u2, e2)
        ]

    def test_edge_exponents(self):
        eng = mm.ModEngine("tpu")
        assert eng.pow_batch([7, 7, 0, 1, mm.P - 1], [0, 1, 5, 9, 2]) == [
            1, 7, 0, 1, pow(mm.P - 1, 2, mm.P)
        ]

    def test_empty_batch(self):
        assert mm.ModEngine("tpu").pow_batch([], []) == []

    def test_limb_roundtrip(self):
        for _ in range(20):
            x = rng.randrange(mm.P)
            assert mm.limbs_to_int(mm.int_to_limbs(x)) == x


class TestShamir:
    def test_lagrange_recovers_secret(self):
        secret = rng.randrange(mm.Q)
        shares = tpke._shamir_shares(
            secret, 7, 3, lambda k: rng.randbytes(k)
        )
        xs = [2, 5, 7]
        lams = tpke.lagrange_coeff_at_zero(xs)
        got = sum(l * shares[x - 1] for l, x in zip(lams, xs)) % mm.Q
        assert got == secret

    def test_fewer_than_threshold_insufficient(self):
        # t-1 shares give a different (wrong) interpolation
        secret = rng.randrange(mm.Q)
        shares = tpke._shamir_shares(secret, 7, 3, lambda k: rng.randbytes(k))
        xs = [1, 4]
        lams = tpke.lagrange_coeff_at_zero(xs)
        got = sum(l * shares[x - 1] for l, x in zip(lams, xs)) % mm.Q
        assert got != secret


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
class TestTpke:
    def _setup(self, backend, n=4, f=1, seed=5):
        pub, shares = tpke.deal(n, f + 1, seed=seed)
        return tpke.Tpke(pub, backend=backend), shares

    def test_encrypt_decrypt_roundtrip(self, backend):
        svc, shares = self._setup(backend)
        msg = b"proposal for epoch 9: " + bytes(range(100))
        ct = svc.encrypt(msg)
        dec = [svc.dec_share(s, ct) for s in shares]
        ok = svc.verify_dec_shares(ct, dec)
        assert ok == [True] * 4
        # any f+1 = 2 shares decrypt
        assert svc.combine(ct, [dec[1], dec[3]]) == msg
        assert svc.combine(ct, [dec[0], dec[2]]) == msg

    def test_bad_share_rejected(self, backend):
        svc, shares = self._setup(backend)
        ct = svc.encrypt(b"secret")
        good = svc.dec_share(shares[0], ct)
        forged = tpke.DhShare(index=2, d=good.d, e=good.e, z=good.z)
        wrong_d = tpke.DhShare(
            index=good.index, d=pow(good.d, 2, mm.P), e=good.e, z=good.z
        )
        oob = tpke.DhShare(index=99, d=good.d, e=good.e, z=good.z)
        ok = svc.verify_dec_shares(ct, [good, forged, wrong_d, oob])
        assert ok == [True, False, False, False]

    def test_share_for_other_ciphertext_rejected(self, backend):
        svc, shares = self._setup(backend)
        ct1 = svc.encrypt(b"one")
        ct2 = svc.encrypt(b"two")
        d1 = svc.dec_share(shares[0], ct1)
        assert svc.verify_dec_shares(ct2, [d1]) == [False]

    def test_tampered_ciphertext_fails_integrity(self, backend):
        svc, shares = self._setup(backend)
        ct = svc.encrypt(b"payload")
        bad = tpke.Ciphertext(
            c1=ct.c1, c2=bytes([ct.c2[0] ^ 1]) + ct.c2[1:], tag=ct.tag
        )
        dec = [svc.dec_share(s, bad) for s in shares[:2]]
        with pytest.raises(ValueError, match="integrity"):
            svc.combine(bad, dec)

    def test_too_few_shares_raises(self, backend):
        svc, shares = self._setup(backend)
        ct = svc.encrypt(b"x")
        with pytest.raises(ValueError, match="need >="):
            svc.combine(ct, [svc.dec_share(shares[0], ct)])


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
class TestCommonCoin:
    def test_agreement_across_share_subsets(self, backend):
        n, f = 7, 2
        pub, shares = tpke.deal(n, f + 1, seed=11)
        c = coin_mod.CommonCoin(pub, backend=backend)
        cid = b"epoch3|proposer5|round0"
        all_shares = [c.share(s, cid) for s in shares]
        assert c.verify_shares(cid, all_shares) == [True] * n
        v1 = c.combine(cid, all_shares[:3])
        v2 = c.combine(cid, all_shares[4:7])
        v3 = c.combine(cid, [all_shares[0], all_shares[3], all_shares[6]])
        assert v1 == v2 == v3

    def test_different_ids_differ(self, backend):
        pub, shares = tpke.deal(4, 2, seed=12)
        c = coin_mod.CommonCoin(pub, backend=backend)
        vals = set()
        for r in range(8):
            cid = b"round|%d" % r
            sh = [c.share(s, cid) for s in shares[:2]]
            vals.add(c.toss(cid, sh))
        assert vals == {True, False}  # 8 tosses, both outcomes seen

    def test_bad_coin_share_rejected(self, backend):
        pub, shares = tpke.deal(4, 2, seed=13)
        c = coin_mod.CommonCoin(pub, backend=backend)
        cid = b"cid"
        good = c.share(shares[0], cid)
        evil = tpke.DhShare(index=1, d=good.d, e=good.e, z=(good.z + 1) % mm.Q)
        assert c.verify_shares(cid, [good, evil]) == [True, False]


def test_keys_distinct_between_tpke_and_coin_seeds():
    pub_a, _ = tpke.deal(4, 2, seed=1)
    pub_b, _ = tpke.deal(4, 2, seed=2)
    assert pub_a.master != pub_b.master


class TestGroupMembership:
    """ADVICE.md round-1 high finding: ciphertext c1 values outside the
    prime-order subgroup must be rejected before share issuance."""

    def test_rejects_non_members(self):
        for bad in (0, 1, mm.P - 1, mm.P, mm.P + 5):
            assert not tpke.is_group_element(bad)

    def test_rejects_non_residue(self):
        # a generator of the full group Z_p* is not a QR; find one by
        # scanning small values (p = 2q+1 safe prime: non-residues have
        # order 2q, i.e. x^q == -1)
        x = next(
            x for x in range(2, 100) if pow(x, mm.Q, mm.P) == mm.P - 1
        )
        assert not tpke.is_group_element(x)

    def test_accepts_honest_values(self):
        assert tpke.is_group_element(mm.G)
        pub, _ = tpke.deal(4, 2, seed=3)
        assert tpke.is_group_element(pub.master)
        ct = tpke.Tpke(pub).encrypt(b"m")
        assert tpke.is_group_element(ct.c1)

    def test_deserialize_rejects_poisoned_c1(self):
        import struct

        import pytest

        from cleisthenes_tpu.protocol.honeybadger import (
            deserialize_ciphertext,
            serialize_ciphertext,
        )

        c2 = b"\x00" * 8
        for bad_c1 in (0, 1, mm.P - 1):
            blob = (
                bad_c1.to_bytes(32, "big")
                + struct.pack(">I", len(c2))
                + c2
                + b"\x11" * 32
            )
            with pytest.raises(ValueError):
                deserialize_ciphertext(blob)
        # round-trip of an honest ciphertext still works
        pub, _ = tpke.deal(4, 2, seed=5)
        ct = tpke.Tpke(pub).encrypt(b"honest")
        assert deserialize_ciphertext(serialize_ciphertext(ct)) == ct


class TestBatchedChallenge:
    """The batched CP-challenge path (ops/hashrows + _cp_challenge_batch)
    must stay byte-identical to the scalar _hash_to_int transcript —
    this equivalence is what lets shares issued by the batched path
    verify under the scalar path and vice versa."""

    def test_cp_challenge_batch_matches_scalar(self):
        import secrets as _s

        gp = mm.DEFAULT_GROUP
        nb = gp.nbytes
        ctxs, bases, his, ds, a1s, a2s = [], [], [], [], [], []
        # m=100 is ABOVE the m<64 scalar cutoff: this must exercise
        # the numpy/native matrix path, not compare the scalar path
        # with itself (a round-4 review caught exactly that vacuity)
        m = 100
        assert m >= 64
        for i in range(m):
            # mixed context lengths exercise the group-by-length path
            ctxs.append(b"ctx|%d" % (10 ** (i % 4)))
            for lst in (bases, his, ds, a1s, a2s):
                lst.append(int.from_bytes(_s.token_bytes(nb), "big") % gp.p)
        got = tpke._cp_challenge_batch(ctxs, bases, his, ds, a1s, a2s, gp)
        # and the sub-cutoff scalar path agrees on a prefix slice
        got_small = tpke._cp_challenge_batch(
            ctxs[:8], bases[:8], his[:8], ds[:8], a1s[:8], a2s[:8], gp
        )
        assert got_small == got[:8]
        for k in range(m):
            want = (
                tpke._hash_to_int(
                    b"cp", ctxs[k],
                    tpke._ibytes(bases[k], nb), tpke._ibytes(his[k], nb),
                    tpke._ibytes(ds[k], nb), tpke._ibytes(a1s[k], nb),
                    tpke._ibytes(a2s[k], nb),
                )
                % gp.q
            )
            assert got[k] == want

    def test_batched_issue_verifies_under_scalar_path(self):
        pub, shares = tpke.deal(n=5, threshold=2, seed=77)
        base = tpke.hash_to_group(b"cross-check")
        ctx = b"cross|ctx"
        out = tpke.issue_shares_batch(
            [(s, base, ctx, pub.verification_keys[s.index - 1]) for s in shares]
        )
        # scalar verifier accepts every batched-issued share
        assert all(tpke.verify_shares(pub, base, out, ctx))
        # and the scalar-issued share verifies under the batched path
        one = tpke.issue_share(shares[0], base, ctx)
        v, _, _ = tpke.verify_and_combine_share_groups(
            [(pub, base, [one] + out[1:], ctx)], 2
        )
        assert all(v[0])


class TestFusedVerifyCombine:
    def test_fused_matches_separate_ops(self):
        pub, shares = tpke.deal(n=7, threshold=3, seed=42)
        groups = []
        for i in range(4):
            ctx = b"g|%d" % i
            base = tpke.hash_to_group(b"b|%d" % i)
            out = tpke.issue_shares_batch(
                [(s, base, ctx, pub.verification_keys[s.index - 1])
                 for s in shares]
            )
            groups.append((pub, base, out, ctx))
        v1 = tpke.verify_share_groups(groups)
        c1 = tpke.combine_shares_batch([g[2][:3] for g in groups], 3)
        tpke._COMBINE_MEMO.clear()
        v2, c2, _ = tpke.verify_and_combine_share_groups(groups, 3)
        assert v1 == v2 and c1 == c2
        # memo is seeded: a follow-up scalar combine is a pure hit
        assert tpke.combine_shares(groups[0][2][:3], 3) == c2[0]

    def test_fused_combine_only_sets(self):
        pub, shares = tpke.deal(n=6, threshold=3, seed=43)
        base = tpke.hash_to_group(b"co")
        ctx = b"co|ctx"
        out = tpke.issue_shares_batch(
            [(s, base, ctx, pub.verification_keys[s.index - 1])
             for s in shares]
        )
        want = tpke.combine_shares_batch([out[:3], out[2:5]], 3)
        tpke._COMBINE_MEMO.clear()
        # equal-but-distinct group object must still combine (keyed by
        # value, not identity)
        gp2 = mm.GroupParams(p=mm.P, q=mm.Q, g=mm.G)
        v, gvals, co = tpke.verify_and_combine_share_groups(
            [(pub, base, out, ctx)],
            3,
            combine_only_sets=[out[:3], out[2:5]],
            combine_only_group=gp2,
        )
        assert all(v[0])
        assert co == want

    def test_fused_flags_tampered_share(self):
        pub, shares = tpke.deal(n=5, threshold=2, seed=44)
        base = tpke.hash_to_group(b"tamper")
        ctx = b"t|ctx"
        out = tpke.issue_shares_batch(
            [(s, base, ctx, pub.verification_keys[s.index - 1])
             for s in shares]
        )
        bad = list(out)
        bad[2] = tpke.DhShare(
            index=bad[2].index, d=bad[2].d, e=bad[2].e, z=bad[2].z + 1
        )
        v, _, _ = tpke.verify_and_combine_share_groups(
            [(pub, base, bad, ctx)], 2
        )
        assert v[0] == [True, True, False, True, True]


class TestShareColumns:
    """A wave as byte columns (ShareColumns) from issue to verify to
    combine: the list path's shares, transcripts, verdicts and values,
    with no DhShare made on the way."""

    N, T = 7, 3

    def _wave(self, seed, k=4, tag=b"w"):
        pub, secs = tpke.deal(n=self.N, threshold=self.T, seed=seed)
        pairs = [
            # mixed context lengths: the transcript's group-by-length
            (tpke.hash_to_group(b"%s|%d" % (tag, j)), b"c|%d" % (10 ** j))
            for j in range(k)
        ]
        vks = [pub.verification_keys[s.index - 1] for s in secs]
        return pub, secs, tpke.ShareWave(secs, vks, pairs)

    def _groups(self, pub, wave, shares, rows=None):
        n = len(wave.secrets)
        rows = n if rows is None else rows
        return [
            (pub, base, shares[j * n : j * n + rows], ctx)
            for j, (base, ctx) in enumerate(wave.pairs)
        ]

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_columnar_issue_is_the_list_issue(self, backend):
        """Same d as host_pow, index order as documented, and every
        columnar share passes the LIST verifier."""
        pub, secs, wave = self._wave(61)
        tpke.reset_share_tally()
        cols = tpke.issue_share_columns([wave], backend=backend)
        assert tpke.share_tally() == {
            "shares_issued_columnar": 4 * self.N,
            "shares_issued_listed": 0,
            "shares_materialized": 0,
        }
        assert len(cols) == 4 * self.N
        assert cols.d.shape == (4 * self.N, mm.DEFAULT_GROUP.nbytes)
        shares = cols.to_shares()
        assert tpke.share_tally()["shares_materialized"] == 4 * self.N
        for j, (base, _ctx) in enumerate(wave.pairs):
            for i, sec in enumerate(secs):
                sh = shares[j * self.N + i]
                assert sh.index == sec.index
                assert sh.d == mm.host_pow(base, sec.value)
        assert tpke.verify_share_groups(
            self._groups(pub, wave, shares), backend
        ) == [[True] * self.N] * 4

    def test_list_issued_shares_pass_the_columnar_verifier(self):
        pub, secs, wave = self._wave(62)
        listed = tpke.issue_shares_batch(
            [
                (sec, base, ctx, vk)
                for base, ctx in wave.pairs
                for sec, vk in zip(wave.secrets, wave.vks)
            ]
        )
        cols = tpke.ShareColumns.from_shares(listed)
        v, vals, _ = tpke.verify_and_combine_share_groups(
            self._groups(pub, wave, cols), self.T
        )
        assert v == [[True] * self.N] * 4
        assert vals == tpke.combine_shares_batch(
            [g[2] for g in self._groups(pub, wave, listed, self.T)], self.T
        )

    def test_two_waves_in_one_dispatch_and_the_empty_wave(self):
        """The lockstep round 0 shape: a coin wave and a decrypt wave
        under different key sets, one call, rows wave after wave."""
        pub_a, _sa, wave_a = self._wave(63, k=2, tag=b"a")
        pub_b, _sb, wave_b = self._wave(64, k=3, tag=b"b")
        cols = tpke.issue_share_columns([wave_a, wave_b])
        na = 2 * self.N
        assert len(cols) == 5 * self.N
        shares = cols.to_shares()
        assert tpke.verify_share_groups(
            self._groups(pub_a, wave_a, shares[:na])
            + self._groups(pub_b, wave_b, shares[na:])
        ) == [[True] * self.N] * 5
        assert len(tpke.issue_share_columns([])) == 0
        assert len(tpke.issue_share_columns([wave_a._replace(pairs=[])])) == 0

    @pytest.mark.parametrize("m", [5, 100])
    def test_cp_challenge_cols_is_hash_to_int(self, m):
        """The column transcript is byte-identical to _hash_to_int's,
        over mixed context lengths and runs, on both sides of the int
        form's 64-row line."""
        import numpy as np
        import secrets as _s

        from cleisthenes_tpu.ops.hashrows import be_rows_to_ints

        gp = mm.DEFAULT_GROUP
        nb = gp.nbytes
        reps = [(3, 1, 2, 4)[j % 4] for j in range(m)]
        ctxs = [b"ctx|%d" % (10 ** (j % 5)) for j in range(m)]
        rows = sum(reps)
        cols = [
            np.frombuffer(_s.token_bytes(rows * nb), dtype=np.uint8)
            .reshape(rows, nb)
            for _ in range(5)
        ]
        digs = tpke._cp_challenge_cols(ctxs, reps, cols, gp)
        assert digs.shape == (rows, 32)
        row_ctx = [c for c, r in zip(ctxs, reps) for _ in range(r)]
        want = [
            tpke._hash_to_int(
                b"cp", row_ctx[i], *(col[i].tobytes() for col in cols)
            )
            for i in range(rows)
        ]
        assert be_rows_to_ints(digs) == want
        # and the int form, now a wrapper on the same layout
        ints = [be_rows_to_ints(col) for col in cols]
        assert tpke._cp_challenge_batch(row_ctx, *ints, gp) == [
            w % gp.q for w in want
        ]

    def test_to_shares_round_trips_and_slices_are_views(self):
        _pub, _secs, wave = self._wave(65)
        cols = tpke.issue_share_columns([wave])
        shares = cols.to_shares()
        back = tpke.ShareColumns.from_shares(shares)
        for name in ("index", "d", "e", "z"):
            assert (getattr(back, name) == getattr(cols, name)).all()
        part = cols[self.N : self.N + 3]
        assert len(part) == 3 and part.d.base is not None
        assert part.to_shares() == shares[self.N : self.N + 3]
        assert part.group is cols.group

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_columnar_verify_combine_is_the_list_form(self, backend):
        pub, _secs, wave = self._wave(66)
        cols = tpke.issue_share_columns([wave], backend=backend)
        listed = cols.to_shares()
        co = [cols[0 : self.T], cols[self.N + 2 : self.N + 2 + self.T]]
        co_listed = [listed[0 : self.T], listed[self.N + 2 : self.N + 2 + self.T]]
        tpke._COMBINE_MEMO.clear()
        want = tpke.verify_and_combine_share_groups(
            self._groups(pub, wave, listed, self.T + 1), self.T,
            backend=backend, combine_only_sets=co_listed,
        )
        tpke._COMBINE_MEMO.clear()
        tpke.reset_share_tally()
        got = tpke.verify_and_combine_share_groups(
            self._groups(pub, wave, cols, self.T + 1), self.T,
            backend=backend, combine_only_sets=co,
        )
        assert got == want
        assert all(all(v) for v in got[0])
        assert tpke.share_tally()["shares_materialized"] == 0
        # the memo is seeded under the list form's keys: a combine on
        # either form of the subset is a pure hit
        g0 = self._groups(pub, wave, cols, self.T + 1)[0][2]
        assert tpke.combine_shares(g0, self.T) == got[1][0]
        assert tpke.combine_shares(g0.to_shares(), self.T) == got[1][0]
        # a group under the threshold has no value; combine-only alone
        # needs its group named
        v, vals, _ = tpke.verify_and_combine_share_groups(
            self._groups(pub, wave, cols, self.T - 1), self.T
        )
        assert vals == [None] * 4 and all(all(x) for x in v)
        with pytest.raises(ValueError):
            tpke.verify_and_combine_share_groups(
                [], self.T, combine_only_sets=co
            )
        assert tpke.verify_and_combine_share_groups(
            [], self.T, combine_only_sets=co,
            combine_only_group=mm.DEFAULT_GROUP,
        )[2] == want[2]

    FORGERIES = {
        "wrong_e": lambda sh, p: sh._replace(e=sh.e ^ 1),
        "wrong_z": lambda sh, p: sh._replace(z=sh.z + 1),
        "wrong_d": lambda sh, p: sh._replace(d=sh.d * 4 % p),
        "d_zero": lambda sh, p: sh._replace(d=0),
        "d_over_p": lambda sh, p: sh._replace(d=sh.d + p),
        "index_zero": lambda sh, p: sh._replace(index=0),
        "index_past_n": lambda sh, p: sh._replace(index=8),
    }

    @pytest.mark.parametrize("kind", sorted(FORGERIES))
    def test_columnar_verifier_reads_each_forgery_false(self, kind):
        """Every forgery kind reads False in the columnar form, as in
        the list form, and only at its own row."""
        pub, secs, wave = self._wave(67, k=2)
        bad_at = 2
        if kind == "d_over_p":
            # d + p passes every check but 0 < d < p, and fits the
            # column's 32 bytes for one d in ~330: find such a base
            for j in range(10_000):
                base = tpke.hash_to_group(b"small-d|%d" % j)
                if mm.host_pow(base, secs[bad_at].value) + mm.P < 1 << 256:
                    break
            wave = wave._replace(pairs=[(base, b"c|x"), wave.pairs[1]])
        listed = tpke.issue_share_columns([wave]).to_shares()
        bad = list(listed)
        bad[bad_at] = self.FORGERIES[kind](listed[bad_at], mm.P)
        want = [[True] * self.N, [True] * self.N]
        want[0][bad_at] = False
        groups = self._groups(pub, wave, bad)
        assert tpke.verify_share_groups(groups) == want
        v, _vals, _ = tpke.verify_and_combine_share_groups(
            self._groups(pub, wave, tpke.ShareColumns.from_shares(bad)),
            self.T,
        )
        assert v == want

    def test_columnar_combine_rejects_duplicate_indices(self):
        pub, _secs, wave = self._wave(68, k=1)
        cols = tpke.issue_share_columns([wave])
        dup = cols[[0, 0, 1, 2]]
        with pytest.raises(ValueError, match="duplicate"):
            tpke.verify_and_combine_share_groups(
                [(pub, wave.pairs[0][0], dup, wave.pairs[0][1])], self.T
            )
        with pytest.raises(ValueError, match="duplicate"):
            tpke.combine_shares(dup, self.T)
        with pytest.raises(ValueError, match="need >="):
            tpke.combine_shares(cols[:2], self.T)

    def test_mixed_forms_fall_back_to_the_list_form(self):
        """Columns beside a DhShare list in one call: the columns are
        materialised, the answer is the list form's."""
        pub, _secs, wave = self._wave(69, k=2)
        cols = tpke.issue_share_columns([wave])
        groups = self._groups(pub, wave, cols)
        mixed = [groups[0], groups[1][:2] + (groups[1][2].to_shares(),) + groups[1][3:]]
        tpke.reset_share_tally()
        v, vals, _ = tpke.verify_and_combine_share_groups(mixed, self.T)
        assert v == [[True] * self.N] * 2
        assert tpke.share_tally()["shares_materialized"] == self.N
        assert vals == tpke.verify_and_combine_share_groups(groups, self.T)[1]


class TestCombineWaveChunks:
    """``combine_share_wave`` feeds the engine ``COMBINE_CHUNK_ROWS``
    rows at a time and pads a last chunk of half as many or more up to
    it, so a device-bound chunk has one shape whatever the wave."""

    @staticmethod
    def _sets(count, seed):
        pub, shares = tpke.deal(n=7, threshold=3, seed=seed)
        sets = []
        for i in range(count):
            ctx = b"chunk|%d" % i
            base = tpke.hash_to_group(ctx)
            out = tpke.issue_shares_batch(
                [(s, base, ctx, pub.verification_keys[s.index - 1])
                 for s in shares]
            )
            # every validator its own first-arrived subset
            sets.append(out[i % 4 : i % 4 + 3 + i % 2])
        return sets

    @pytest.mark.parametrize(
        "backend,count,calls",
        [
            ("cpu", 5, 1),  # 15 rows: one chunk, padded to 16
            ("cpu", 11, 3),  # 33 rows: 16 + 16 + a 1-row tail as it is
            ("tpu", 5, 1),
            ("tpu", 11, 3),
        ],
    )
    def test_chunked_wave_matches_scalar(
        self, monkeypatch, backend, count, calls
    ):
        monkeypatch.setattr(tpke, "COMBINE_CHUNK_ROWS", 16)
        # the device programs, at toy size: the floors pinned off
        monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
        sets = self._sets(count, seed=60 + count)
        shapes = []
        real = mm.ModEngine.pow_cols

        def spy(eng, base_b, exp_b):
            shapes.append(len(base_b))
            return real(eng, base_b, exp_b)

        monkeypatch.setattr(mm.ModEngine, "pow_cols", spy)
        tpke._COMBINE_MEMO.clear()
        vals, dispatches, hits = tpke.combine_share_wave(
            sets + sets[:2], [3] * (count + 2), backend=backend
        )
        assert dispatches == calls == len(shapes)
        assert hits == 2  # the two repeated sets ride their twins' rows
        assert shapes == ([16] if count == 5 else [16, 16, 1])
        tpke._COMBINE_MEMO.clear()
        assert vals == [tpke.combine_shares(s, 3) for s in sets + sets[:2]]
        # and the memo is seeded for the scalar path
        assert len(tpke._COMBINE_MEMO) == count

    def test_wide_group_takes_the_int_entry_point(self):
        pub, shares = tpke.deal(n=4, threshold=2, seed=71, group=mm.GROUP384)
        base = tpke.hash_to_group(b"wide", mm.GROUP384)
        out = tpke.issue_shares_batch(
            [(s, base, b"w", pub.verification_keys[s.index - 1])
             for s in shares],
            group=mm.GROUP384,
        )
        tpke._COMBINE_MEMO.clear()
        vals, dispatches, hits = tpke.combine_share_wave(
            [out[:2], out[1:3]], [2, 2], mm.GROUP384
        )
        assert (dispatches, hits) == (1, 0)
        tpke._COMBINE_MEMO.clear()
        assert vals == [
            tpke.combine_shares(s, 2, mm.GROUP384)
            for s in (out[:2], out[1:3])
        ]
