"""EchoBank: vectorized ECHO/READY receipt state across RBC instances.

An epoch runs N concurrent RBC instances (one per proposer,
docs/HONEYBADGER-EN.md:85-89), and within one wave a sender emits one
ECHO and one READY per instance — the coalescer ships them as ONE
columnar payload each (transport.message EchoBatchPayload /
ReadyBatchPayload).  A delivery wave therefore hands a receiver up to
N such frames of N items: N^2 receipts a receiver, N^3 a round.

The bank is the VoteBank treatment applied to RBC: one
struct-of-arrays per ACS holding every instance's ECHO/READY receipt
state, and one WAVE entry a message kind (``wave_echo`` /
``wave_ready``, as ``VoteBank.wave_vote``): membership, delivered-
instance and seen-bit filters, intra-wave duplicate (sender, instance)
pairs, the structural precheck, the slot claim and the quorum counting
run over the concatenation of all senders' rows — senders x instances
wide — and only threshold CROSSINGS (f+1 READY relay, q_large deliver
probe, the N-f echo-potential flush request — a constant number per
instance) reach the per-instance protocol logic in RBC, after the
wave's adds have landed, in first-arrival order.

What a received ECHO is between the router and its Merkle verdict is
a FRAME record (``EchoFrame``): one per surviving (sender, payload) —
the sender, its shard index, the kept instance / position / root-row /
shard-length arrays, and the payload's own roots / branches / shards
tuples by reference.  Frames park in arrival order, the hub's branch
column takes each one whole (``HubWave.add_branch_frame``), and the
verdicts come back as (frames, boolean arrays): verified-echo counts,
burned-claim decrements, the verified shard-length authority and the
N-f quorum all update on the arrays.  An instance's ``k`` shards are
gathered from the frames only when it stages its decode.

The part of the structural precheck that does not depend on the
receiver (root, shard and branch shapes) is decided once a wire
payload: the codec's payload memo hands every receiver the same
``roots`` / ``branches`` / ``shards`` tuples, so ``_echo_shape`` /
``_ready_shape`` memoize on their identity.  Roots resolve to registry
rows by one vectorized compare against each instance's PRIMARY root
(the first one registered for it — the only one, unless its proposer
equivocates); only the others take a dict probe an item.

Array layouts put the wave's axis LAST: receipt state is indexed
``seen[sender, instance]`` so one frame's dedup probe is a contiguous
row, and delivered/halted instances fold into ONE ``state`` vector (a
huge sentinel — every later delivery for them drops in the same
vectorized filter, before any python-level dispatch).

Quorum counting is per (root, instance): distinct Merkle roots map to
rows of the counting matrices through a registry, so a Byzantine
proposer equivocating different roots to different receivers keeps
fully separate counters — the bank can never conflate two roots'
quorums (the PR-4 Equivocator coalition runs against exactly this).
Registry growth is bounded by the one-vote-per-(sender, instance)
claim discipline: at most senders x instances distinct roots can ever
be counted (plus one a VAL).

Consistency contract: the bank is the SINGLE source of truth for
ECHO/READY receipt state.  RBC's per-payload entry points (VAL
leaves, self-delivery, unit tests, rows that repeat an instance) write
the same arrays and park the same frame records (of width 1), so wave
and per-payload deliveries interleave freely.

Quorum semantics mirrored from RBC (docs/RBC-EN.md:35-42): one vote
per (sender, instance) makes counts advance in +1 steps, so a wave's
crossing is the item whose running count equals the threshold; the
deliver probe stays >= because decode completion re-probes ride later
arrivals.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from cleisthenes_tpu.utils.memo import BoundedFifoMemo

# Per-root shard length sanity cap (a Byzantine proposer must not make
# honest nodes buffer huge shards; envelopes are separately capped by
# transport.message.MAX_FIELD_BYTES).
MAX_SHARD_BYTES = 16 * 1024 * 1024

# Byzantine batches can mint unlimited distinct proposer tuples; the
# index cache clears wholesale at the cap (honest traffic reuses a
# handful of tuples per wave).
_PROP_CACHE_CAP = 4096

# state sentinel for delivered/halted instances: live instances sit at
# 0, so one vectorized compare drops every late vote for a terminal
# instance (same discipline as votebank._HALTED)
_HALTED = 1 << 62

# id-keyed payload-shape memos: an entry holds the payload's tuples
# (pinning the ids against recycling, the hub's token-table
# discipline), FIFO-bounded like the transport's decode memo, whose
# entries are the same objects
_ECHO_SHAPES = BoundedFifoMemo(1024)
_READY_SHAPES = BoundedFifoMemo(1024)

_ZERO_ROOT = bytes(32)


def tree_depth(n: int) -> int:
    """Depth of the padded Merkle tree over ``n`` shards."""
    p, depth = 1, 0
    while p < n:
        p <<= 1
        depth += 1
    return depth


def _root_matrix(roots: tuple, ok: Optional[list]) -> np.ndarray:
    """(len, 4) uint64 view of 32-byte roots, zero rows where
    malformed — one vectorized compare resolves a wave's roots."""
    if ok is not None:
        roots = [r if good else _ZERO_ROOT for r, good in zip(roots, ok)]
    return np.frombuffer(b"".join(roots), dtype=np.uint64).reshape(-1, 4)


def _echo_shape(roots: tuple, branches: tuple, shards: tuple, depth: int):
    """The receiver-independent half of RBC's structural precheck for
    one ECHO payload's columns: ``(ok, root matrix, shard lengths)``,
    ``ok`` None when every item is well formed (the honest shape), else
    a boolean array.  Memoized on the identity of the tuples the
    codec's payload memo shares across a broadcast's receivers, so it
    runs once a wire payload; width-1 rows are fresh tuples a delivery
    and bypass the memo (they could never hit and would churn it)."""
    wide = len(roots) > 1
    if wide:
        ent = _ECHO_SHAPES.map.get(id(branches))
        if (
            ent is not None
            and ent[0] is branches
            and ent[1] is roots
            and ent[2] is shards
            and ent[3] == depth
        ):
            return ent[4]
    lens = np.fromiter(
        (len(s) for s in shards), dtype=np.int64, count=len(shards)
    )
    good = [
        len(r) == 32
        and 0 < len(s) <= MAX_SHARD_BYTES
        and len(b) == depth
        and all(len(x) == 32 for x in b)
        for r, b, s in zip(roots, branches, shards)
    ]
    ok = None if all(good) else good
    shape = (
        None if ok is None else np.asarray(ok, dtype=bool),
        _root_matrix(roots, ok),
        lens,
    )
    if wide:
        _memo_put(
            _ECHO_SHAPES, id(branches), (branches, roots, shards, depth, shape)
        )
    return shape


def _ready_shape(roots: tuple):
    """``(ok, root matrix)`` of one READY payload's roots column
    (malformed roots drop before any slot claim); memoized like
    ``_echo_shape``."""
    wide = len(roots) > 1
    if wide:
        ent = _READY_SHAPES.map.get(id(roots))
        if ent is not None and ent[0] is roots:
            return ent[1]
    good = [len(r) == 32 for r in roots]
    ok = None if all(good) else good
    shape = (
        None if ok is None else np.asarray(ok, dtype=bool),
        _root_matrix(roots, ok),
    )
    if wide:
        _memo_put(_READY_SHAPES, id(roots), (roots, shape))
    return shape


def _memo_put(memo: BoundedFifoMemo, key, val) -> None:
    # the memos are the process's: two hosts' dispatcher threads in one
    # process (tests) may evict at once, and a lost put is one recompute
    try:
        memo.put(key, val)
    except (KeyError, RuntimeError):
        pass


def _row_masks(oks: list, sizes: list) -> Optional[np.ndarray]:
    """Per-row structural masks (None = every item well formed) as
    one mask over the concatenated rows; None when no row has one."""
    if all(ok is None for ok in oks):
        return None
    return np.concatenate(
        [
            np.ones(m, dtype=bool) if ok is None else ok
            for ok, m in zip(oks, sizes)
        ]
    )


def _concat_rows(batch: list) -> tuple:
    """A wave's rows — ``(sender index, instances, positions, ...)``
    each — as aligned columns over all their items: per-row sizes,
    sender, row number, instance, position."""
    sizes = [row[1].size for row in batch]
    return (
        sizes,
        np.repeat([row[0] for row in batch], sizes),
        np.repeat(np.arange(len(batch)), sizes),
        np.concatenate([row[1] for row in batch]),
        np.concatenate([row[2] for row in batch]),
    )


def _first_seen(values: np.ndarray, size: int) -> np.ndarray:
    """Distinct entries of ``values`` (ints below ``size``) in order
    of first appearance."""
    first = np.empty(size, dtype=np.int64)
    # reversed fancy assignment: the last write is the first arrival
    first[values[::-1]] = np.arange(values.size - 1, -1, -1)
    hit = np.zeros(size, dtype=bool)
    hit[values] = True
    uniq = np.flatnonzero(hit)
    return uniq[np.argsort(first[uniq], kind="stable")]


class EchoFrame:
    """One sender's surviving ECHO items of one payload, parked
    between the claim and the Merkle verdict: the kept instances, their
    positions in the payload's columns, the registry rows their roots
    claimed and their shard lengths (aligned arrays), and the
    payload's own tuples by reference.  The hub's branch column takes
    the record whole and answers with one boolean array."""

    __slots__ = (
        "bank", "si", "shard_index", "pi", "pos", "rows", "lens",
        "roots", "branches", "shards",
    )

    def __init__(
        self, bank, si, shard_index, pi, pos, rows, lens,
        roots, branches, shards,
    ) -> None:
        self.bank = bank
        self.si = si
        self.shard_index = shard_index
        self.pi = pi
        self.pos = pos
        self.rows = rows
        self.lens = lens
        self.roots = roots
        self.branches = branches
        self.shards = shards

    def kept(self, keep: np.ndarray) -> "EchoFrame":
        return EchoFrame(
            self.bank, self.si, self.shard_index, self.pi[keep],
            self.pos[keep], self.rows[keep], self.lens[keep],
            self.roots, self.branches, self.shards,
        )


class EchoBank:
    """Struct-of-arrays ECHO/READY receipt state for up to ``n_inst``
    RBC instances over a fixed roster."""

    def __init__(
        self,
        member_ids: Sequence[str],
        f: int,
        inst_ids: Optional[Sequence[str]] = None,
        metrics=None,
        quorum_large: Optional[int] = None,
    ) -> None:
        self.members: List[str] = sorted(member_ids)
        self.f = f
        # the READY deliver threshold: 2f+1 in the baseline trust
        # model, n-f under Config.reduced_quorum (identical whenever
        # n = 3f+1 exactly — see Config.quorum_large)
        self.q_large = 2 * f + 1 if quorum_large is None else quorum_large
        # owner-node metrics (None in standalone unit tests): the
        # duplicate-vote absorption counter and the wave-engagement
        # counter (echo_items_wave) are touched here
        self.metrics = metrics
        self.sidx: Dict[str, int] = {
            m: i for i, m in enumerate(self.members)
        }
        insts = self.members if inst_ids is None else list(inst_ids)
        self.iidx: Dict[str, int] = {p: i for i, p in enumerate(insts)}
        ns, n_inst = len(self.members), len(insts)
        self.n = ns
        self.n_inst = n_inst
        # depth of the padded tree a proposer must have built
        self.depth = tree_depth(ns)
        # [sender, instance]: one frame's dedup probe is a contiguous
        # row (wave axis last, like votebank.bval_seen)
        self.echo_seen = np.zeros((ns, n_inst), dtype=bool)
        self.ready_seen = np.zeros((ns, n_inst), dtype=bool)
        # 0 = live; _HALTED once the instance delivered — the
        # vectorized stale filter every wave entry applies first
        self.state = np.zeros(n_inst, dtype=np.int64)
        self.rbcs: List[object] = [None] * n_inst
        # claimed, hub-unverified ECHO frames in arrival order; the
        # first RBC the hub drains hands them all to the wave.
        # has_parked[instance]: some parked frame holds an item of it
        # (its RBC is on the hub's dirty list until drained)
        self.parked: List[EchoFrame] = []
        self.has_parked = np.zeros(n_inst, dtype=bool)
        # root registry: distinct root bytes -> row of the counting
        # matrices.  Bounded by the claim discipline (a row is only
        # ever allocated for a vote that claimed its one
        # (sender, instance) slot, or for an instance's one VAL), so
        # <= senders x instances + instances rows.
        self._root_rows: Dict[bytes, int] = {}
        self._row_roots: List[bytes] = []
        cap0 = max(4, n_inst)
        # [root_row, instance] matrices, wave axis last: echo_pot
        # counts CLAIMED echoes (pending + verified — the flush-trigger
        # potential), echo_ok branch-VERIFIED ones (the N-f quorum),
        # ready_cnt distinct READY senders; shard_len is the root's
        # verified shard length at that instance (0 = none yet — only
        # a verified VAL or ECHO ever writes it, so an unverified
        # Byzantine ECHO cannot poison the expectation)
        self.echo_pot = np.zeros((cap0, n_inst), dtype=np.int32)
        self.echo_ok = np.zeros((cap0, n_inst), dtype=np.int32)
        self.ready_cnt = np.zeros((cap0, n_inst), dtype=np.int32)
        self.shard_len = np.zeros((cap0, n_inst), dtype=np.int64)
        # each instance's PRIMARY root — the first registered for it —
        # as its registry row (-1 = none) and as 4 uint64 words: a
        # wave's roots resolve to rows by one compare against these
        self.primary_row = np.full(n_inst, -1, dtype=np.int64)
        self.primary_mat = np.zeros((n_inst, 4), dtype=np.uint64)
        # the one (root row, instance) that reached N-f verified
        # echoes (-1 = none): two roots cannot both, each sender has
        # one vote and 2(N-f) > N
        self.quorum_row = np.full(n_inst, -1, dtype=np.int64)
        # [sender, instance] -> where that sender's VERIFIED echo
        # lives: the root row it verified under (-1 = none), the
        # retained frame, the position in the frame's payload, the
        # shard index.  Read only when an instance stages its decode.
        self.ver_row = np.full((ns, n_inst), -1, dtype=np.int64)
        self.src_frame = np.zeros((ns, n_inst), dtype=np.int64)
        self.src_pos = np.zeros((ns, n_inst), dtype=np.int64)
        self.src_sidx = np.zeros((ns, n_inst), dtype=np.int64)
        self.frames: List[EchoFrame] = []
        # instances a verdict pass verified an echo for, until their
        # RBC's after_branch_verdicts reads the flag
        self.verdict_touched = np.zeros(n_inst, dtype=bool)
        self._prop_cache: Dict[tuple, tuple] = {}

    # -- membership --------------------------------------------------------

    def attach(self, index: int, rbc) -> None:
        self.rbcs[index] = rbc

    def deactivate(self, index: int) -> None:
        """Delivered/halted instance: every later delivery for it
        drops in the vectorized state filter, and its parked items
        leave their frames at the drain (the instance is terminal —
        nothing will ask for their verdicts)."""
        self.state[index] = _HALTED
        self.has_parked[index] = False

    # -- root registry -----------------------------------------------------

    def _row(self, root: bytes) -> int:
        row = self._root_rows.get(root)
        if row is None:
            row = len(self._row_roots)
            self._root_rows[root] = row
            self._row_roots.append(root)
            if row >= self.echo_pot.shape[0]:
                for name in ("echo_pot", "echo_ok", "ready_cnt", "shard_len"):
                    mat = getattr(self, name)
                    setattr(self, name, np.vstack((mat, np.zeros_like(mat))))
        return row

    def _claim_row(self, index: int, root: bytes) -> int:
        """``root``'s registry row, registered if new; the first root
        an instance registers becomes its primary."""
        row = self._row(root)
        if self.primary_row[index] < 0:
            self.primary_row[index] = row
            self.primary_mat[index] = np.frombuffer(root, dtype=np.uint64)
        return row

    def _resolve_rows(
        self, pi: np.ndarray, root_mat: np.ndarray, root_of, register: bool
    ) -> np.ndarray:
        """Registry rows of a wave's (instance, root) items: one
        vectorized compare against the instances' primary roots, a
        dict probe for each item that names another root
        (``root_of(j)`` -> its bytes).  Unknown roots register in
        arrival order when ``register``, else read -1."""
        rows = self.primary_row[pi]
        match = (root_mat == self.primary_mat[pi]).all(axis=1)
        match &= rows >= 0
        if match.all():
            return rows
        rows = np.where(match, rows, -1)
        known = self._root_rows
        for j in np.flatnonzero(~match).tolist():
            root = root_of(j)
            if register:
                rows[j] = self._claim_row(int(pi[j]), root)
            else:
                rows[j] = known.get(root, -1)
        return rows

    # -- scalar write-through (RBC's per-payload path) ---------------------

    def echo_park(
        self,
        index: int,
        sender_idx: int,
        shard_index: int,
        root: bytes,
        branch: tuple,
        shard: bytes,
    ) -> int:
        """Claim one sender's ECHO slot for ``index``, count it against
        ``root`` and park the proof as a frame of width 1; returns the
        new echo potential (pending + verified claims) for the
        (root, instance).  The caller has already passed dedup +
        precheck — a claim is final (an invalid proof burns the
        sender's one slot, reference rbc semantics)."""
        self.echo_seen[sender_idx, index] = True
        row = self._claim_row(index, root)
        self.echo_pot[row, index] += 1
        self.parked.append(
            EchoFrame(
                self, sender_idx, shard_index,
                np.asarray([index], dtype=np.int64),
                np.zeros(1, dtype=np.int64),
                np.asarray([row], dtype=np.int64),
                np.asarray([len(shard)], dtype=np.int64),
                (root,), (branch,), (shard,),
            )
        )
        self.has_parked[index] = True
        return int(self.echo_pot[row, index])

    def ready_add(
        self, index: int, sender_idx: int, root: bytes
    ) -> Optional[int]:
        """Record one READY; returns the new distinct-sender count for
        (root, instance), or None on a duplicate sender."""
        if self.ready_seen[sender_idx, index]:
            if self.metrics is not None:
                self.metrics.dedup_absorbed.inc()
            return None
        self.ready_seen[sender_idx, index] = True
        row = self._claim_row(index, root)
        self.ready_cnt[row, index] += 1
        return int(self.ready_cnt[row, index])

    def ready_count(self, index: int, root: bytes) -> int:
        row = self._root_rows.get(root)
        return 0 if row is None else int(self.ready_cnt[row, index])

    def echo_quorum_root(self, index: int) -> Optional[bytes]:
        """The root N-f senders' verified echoes named at ``index``."""
        row = self.quorum_row[index]
        return None if row < 0 else self._row_roots[row]

    def ready_roots(self, index: int) -> list:
        """Roots with at least one READY receipt for ``index``, in
        registry insertion order (deterministic: rows are handed out
        in arrival order)."""
        rows = np.flatnonzero(self.ready_cnt[: len(self._row_roots), index])
        roots = self._row_roots
        return [roots[r] for r in rows.tolist()]

    def verified_len(self, index: int, root: bytes) -> int:
        """The verified shard length of (root, instance); 0 = none."""
        row = self._root_rows.get(root)
        return 0 if row is None else int(self.shard_len[row, index])

    def set_verified_len(self, index: int, root: bytes, length: int) -> None:
        """A branch-verified VAL names the root's shard length (the
        first verified length stands)."""
        row = self._claim_row(index, root)
        if self.shard_len[row, index] == 0:
            self.shard_len[row, index] = length

    # -- columnar delivery (ACS wave path) ---------------------------------

    def _indices(self, proposers: tuple) -> tuple:
        """(instance index array, source position array, has_dups,
        whole) — computed once per distinct proposers tuple (the
        codec's decode memo shares one tuple across a broadcast's
        receivers, so this builds once per wire payload).  Unknown
        proposers drop at cache build; positions keep the per-instance
        columns (roots, branches, shards) aligned after the drop, and
        ``whole`` says none did.  The arrays are shared by every frame
        of the tuple: read-only."""
        ent = self._prop_cache.get(proposers)
        if ent is None:
            iidx = self.iidx
            pairs = [
                (iidx[p], k)
                for k, p in enumerate(proposers)
                if p in iidx
            ]
            arr = np.asarray([i for i, _k in pairs], dtype=np.int64)
            pos = np.asarray([k for _i, k in pairs], dtype=np.int64)
            arr.flags.writeable = False
            pos.flags.writeable = False
            dups = len(set(proposers)) != len(proposers)
            if len(self._prop_cache) >= _PROP_CACHE_CAP:
                self._prop_cache.clear()
            ent = (arr, pos, dups, len(pairs) == len(proposers))
            self._prop_cache[proposers] = ent
        return ent

    def _absorb(self, count: int) -> None:
        if count and self.metrics is not None:
            self.metrics.dedup_absorbed.inc(count)

    def wave_ready(self, items) -> None:
        """One delivery wave's READYs across ALL senders: each row is
        one sender's ``(sender, proposers, roots)`` fan-out (a
        ReadyBatchPayload, or a width-1 scalar READY).  Membership,
        delivered filter, dedup and per-(root, instance) counting run
        as one concatenated pass; only threshold crossings reach RBC.
        A row that repeats an instance (only Byzantine batches do)
        goes item by item through the scalar gate, in its place in
        the wave, which keeps exact first-vote-wins semantics."""
        batch: list = []
        rbcs = self.rbcs
        for sender, proposers, roots in items:
            si = self.sidx.get(sender)
            if si is None or len(roots) != len(proposers):
                continue
            pi, pos, dups, whole = self._indices(proposers)
            if pi.size == 0:
                continue
            if dups:
                if batch:
                    self._ready_pass(batch)
                    batch = []
                for i, k in zip(pi.tolist(), pos.tolist()):
                    rbc = rbcs[i]
                    if rbc is not None:
                        rbc.handle_ready_root(sender, roots[k])
                continue
            batch.append((si, pi, pos, whole, roots))
        if batch:
            self._ready_pass(batch)

    def _ready_pass(self, batch: list) -> None:
        sizes, si, fid, pi, pos = _concat_rows(batch)
        oks: list = []
        mats: list = []
        for _si, _pi, p, whole, roots in batch:
            ok, mat = _ready_shape(roots)
            if not whole:
                mat = mat[p]
                ok = None if ok is None else ok[p]
            oks.append(ok)
            mats.append(mat)
        root_mat = mats[0] if len(mats) == 1 else np.concatenate(mats)
        keep = self.state[pi] == 0
        # malformed roots drop before any slot claim or dedup tally,
        # exactly like the scalar length gate
        well = _row_masks(oks, sizes)
        if well is not None:
            keep &= well
        seen = self.ready_seen[si, pi]
        seen &= keep
        if seen.any():
            self._absorb(int(seen.sum()))
            keep &= ~seen
        if not keep.all():
            si, fid, pi, pos = si[keep], fid[keep], pi[keep], pos[keep]
            root_mat = root_mat[keep]
            if pi.size == 0:
                return
        n_inst = self.n_inst
        if len({row[0] for row in batch}) != len(batch):
            # a sender twice in one wave (replayed frames): the first
            # (sender, instance) pair votes, the rest are absorbed
            _u, first = np.unique(si * n_inst + pi, return_index=True)
            if first.size != pi.size:
                self._absorb(int(pi.size - first.size))
                first.sort()
                si, fid, pi, pos = si[first], fid[first], pi[first], pos[first]
                root_mat = root_mat[first]
        self.ready_seen[si, pi] = True
        rows = self._resolve_rows(
            pi, root_mat,
            lambda j: batch[fid[j]][4][pos[j]],
            register=True,
        )
        cnt = self.ready_cnt
        before = cnt[rows, pi]
        np.add.at(cnt, (rows, pi), 1)
        q_large = self.q_large
        if int(cnt[rows, pi].max()) <= self.f:
            return
        # the running count each item saw: its pair's count before the
        # wave plus its rank among the wave's items of that pair
        pair = rows * n_inst + pi
        order = np.argsort(pair, kind="stable")
        sorted_pair = pair[order]
        edge = np.concatenate(([True], sorted_pair[1:] != sorted_pair[:-1]))
        starts = np.flatnonzero(edge)
        group = np.cumsum(edge) - 1
        rank = np.empty(pair.size, dtype=np.int64)
        rank[order] = np.arange(pair.size) - starts[group]
        running = before + rank + 1
        # f+1 same READY -> relay once (docs/RBC-EN.md:41); q_large ->
        # deliver probe, once a pair (>=: a READY past the crossing
        # re-probes a decode that completed since).  Fired after ALL
        # of the wave's adds, in the order the rows would have fired
        # them one at a time: a row's relays, then its probes.
        events = [
            (int(fid[j]), 0, j)
            for j in np.flatnonzero(running == self.f + 1).tolist()
        ]
        events += [
            (int(fid[j]), 1, j)
            for j in np.flatnonzero(
                running == np.maximum(q_large, before + 1)
            ).tolist()
        ]
        events.sort()
        rbcs = self.rbcs
        row_roots = self._row_roots
        for _fid, kind, j in events:
            rbc = rbcs[pi[j]]
            if rbc is None or rbc.delivered:
                continue
            root = row_roots[rows[j]]
            if kind:
                rbc._maybe_deliver(root)
            elif rbc._ready_root is None:
                rbc._send_ready(root)

    def wave_echo(self, items) -> None:
        """One delivery wave's ECHOes across ALL senders: each row is
        one sender's ``(sender, shard_index, proposers, roots,
        branches, shards)`` fan-out (an EchoBatchPayload, or a width-1
        scalar ECHO).  Filters, precheck, slot claims and counting run
        as one concatenated pass; the survivors park as one frame a
        row.  A row that repeats an instance (only Byzantine batches
        do) goes item by item through RBC's scalar entry, in its place
        in the wave."""
        batch: list = []
        rbcs = self.rbcs
        for sender, shard_index, proposers, roots, branches, shards in items:
            si = self.sidx.get(sender)
            if si is None or not (
                len(roots) == len(branches) == len(shards) == len(proposers)
            ):
                continue
            pi, pos, dups, whole = self._indices(proposers)
            if pi.size == 0:
                continue
            if dups:
                if batch:
                    self._echo_pass(batch)
                    batch = []
                for i, k in zip(pi.tolist(), pos.tolist()):
                    rbc = rbcs[i]
                    if rbc is not None and not rbc.delivered:
                        rbc.handle_echo_fast(
                            sender, roots[k], branches[k], shards[k],
                            shard_index,
                        )
                continue
            batch.append(
                (si, pi, pos, whole, shard_index, roots, branches, shards)
            )
        if batch:
            self._echo_pass(batch)

    def _echo_pass(self, batch: list) -> None:
        n_rows = len(batch)
        sizes, si, fid, pi, pos = _concat_rows(batch)
        oks: list = []
        mats: list = []
        lens_parts: list = []
        depth, n = self.depth, self.n
        for _si, _pi, p, whole, shard_index, roots, branches, shards in batch:
            ok, mat, lens = _echo_shape(roots, branches, shards, depth)
            if not whole:
                mat, lens = mat[p], lens[p]
                ok = None if ok is None else ok[p]
            if not (0 <= shard_index < n):
                ok = np.zeros(p.size, dtype=bool)
            oks.append(ok)
            mats.append(mat)
            lens_parts.append(lens)
        if n_rows == 1:
            root_mat, lens = mats[0], lens_parts[0]
        else:
            root_mat, lens = np.concatenate(mats), np.concatenate(lens_parts)
        well = _row_masks(oks, sizes)
        keep = self.state[pi] == 0
        seen = self.echo_seen[si, pi]
        seen &= keep
        if seen.any():  # one ECHO per sender
            self._absorb(int(seen.sum()))
            keep &= ~seen
        if not keep.all():
            si, fid, pi, pos = si[keep], fid[keep], pi[keep], pos[keep]
            root_mat, lens = root_mat[keep], lens[keep]
            if pi.size == 0:
                return
            if well is not None:
                well = well[keep]
        if self.metrics is not None:
            self.metrics.echo_items_wave.inc(int(pi.size))

        def root_of(j):
            return batch[fid[j]][5][pos[j]]

        # registry rows WITHOUT registering: an item may still fail the
        # precheck, and only a claim allocates a row
        rows = self._resolve_rows(pi, root_mat, root_of, register=False)
        # shards of one root must agree on length (RS needs a matrix):
        # an item whose length differs from the VERIFIED one fails the
        # precheck (unknown roots have no authority yet)
        want = self.shard_len[np.maximum(rows, 0), pi]
        bad = (rows >= 0) & (want != 0) & (lens != want)
        if bad.any():
            well = ~bad if well is None else well & ~bad
        n_inst = self.n_inst
        if len({row[0] for row in batch}) != n_rows:
            # a sender twice in one wave (replayed frames): a pair's
            # first well-formed item claims, what follows it is
            # absorbed like any seen vote, what precedes it failed its
            # precheck and dropped unseen
            key = si * n_inst + pi
            m = key.size
            cand = np.arange(m) if well is None else np.flatnonzero(well)
            if cand.size == 0:
                return
            uk, first = np.unique(key[cand], return_index=True)
            if uk.size != m:
                at = np.minimum(np.searchsorted(uk, key), uk.size - 1)
                owner = np.where(uk[at] == key, cand[first][at], m)
                idx = np.arange(m)
                self._absorb(int((idx > owner).sum()))
                well = idx == owner
        if well is not None:
            if not well.any():
                return
            if not well.all():
                si, fid, pi, pos = si[well], fid[well], pi[well], pos[well]
                rows, lens = rows[well], lens[well]
        # the claim: slot, registry row, potential
        self.echo_seen[si, pi] = True
        if rows.min() < 0:
            for j in np.flatnonzero(rows < 0).tolist():
                rows[j] = self._claim_row(int(pi[j]), root_of(j))
        pot = self.echo_pot
        np.add.at(pot, (rows, pi), 1)
        # park: one frame a row with survivors, arrival order
        counts = np.bincount(fid, minlength=n_rows)
        parked = self.parked
        at = 0
        for r, c in enumerate(counts.tolist()):
            if not c:
                continue
            row = batch[r]
            end = at + c
            if c == sizes[r]:  # nothing filtered: the cached arrays
                f_pi, f_pos = row[1], row[2]
            else:
                f_pi, f_pos = pi[at:end], pos[at:end]
            parked.append(
                EchoFrame(
                    self, row[0], row[4], f_pi, f_pos, rows[at:end],
                    lens[at:end], row[5], row[6], row[7],
                )
            )
            at = end
        # one dirty mark an instance newly holding parked items, in
        # first-arrival order (the hub drains, and fires quorum logic,
        # in dirty order)
        rbcs = self.rbcs
        fresh = ~self.has_parked[pi]
        if fresh.any():
            self.has_parked[pi] = True
            for i in _first_seen(pi[fresh], n_inst).tolist():
                rbc = rbcs[i]
                if rbc is not None:
                    rbc.hub.mark_dirty(rbc)
        # crossings, after ALL of the wave's adds: the N-f potential
        # asks for a flush (once: the hub's flag), q_large READYs probe
        # delivery (once a pair, first-arrival order)
        row_roots = self._row_roots

        def crossed(hit):
            """(rbc, root) of the distinct (root, instance) pairs among
            the items ``hit`` marks, in first-arrival order."""
            pairs = _first_seen(
                rows[hit] * n_inst + pi[hit], pot.shape[0] * n_inst
            )
            for j in pairs.tolist():
                rbc = rbcs[j % n_inst]
                if rbc is not None:
                    yield rbc, row_roots[j // n_inst]

        hit = pot[rows, pi] >= self.n - self.f
        if hit.any():
            for rbc, root in crossed(hit):
                if rbc._ready_root is None and root not in rbc._bad_roots:
                    rbc.hub.request_flush()
                    break
        hit = self.ready_cnt[rows, pi] >= self.q_large
        if hit.any():
            for rbc, root in crossed(hit):
                rbc._maybe_deliver(root)

    # -- hub drain and verdicts --------------------------------------------

    def drain_parked(self, wave, rbc) -> None:
        """``rbc``'s drain found parked items of its instance: hand
        EVERY parked frame to the hub wave's branch column (the first
        drained instance of a flush does; items of instances that
        delivered since they parked leave their frames here), and note
        ``rbc`` for the per-instance follow-up of the verdicts — the
        hub calls those in drain order."""
        parked = self.parked
        if parked:
            self.parked = []
            live = self.state == 0
            if live.all():
                for frame in parked:
                    wave.add_branch_frame(frame)
            else:
                for frame in parked:
                    keep = live[frame.pi]
                    if keep.all():
                        wave.add_branch_frame(frame)
                    elif keep.any():
                        wave.add_branch_frame(frame.kept(keep))
        self.has_parked[rbc.index] = False
        wave.note_branch_client(rbc)

    def on_branch_verdicts(self, frames: list, oks: list) -> None:
        """Merkle verdicts of this bank's frames, one boolean array a
        frame, frames in arrival order.  A failed proof leaves its
        sender's slot burned but takes the claim out of the quorum
        POTENTIAL — otherwise f parked forgeries would push it past
        N-f forever and every later honest echo would ask for a flush.
        Length authority comes only from verified shards: the first
        verified one of a (root, instance) sets it, and a verified
        shard of another length (a Byzantine proposer mixing lengths
        under one tree) drops like a failed one — RS needs a
        rectangular matrix.  What remains counts toward N-f and is
        remembered by (sender, instance) for the decode.  (No instance
        delivers between a drain and its verdicts, and the drain
        dropped the delivered ones' items.)"""
        base = len(self.frames)
        self.frames.extend(frames)
        sizes = [fr.pi.size for fr in frames]
        if len(frames) == 1:
            fr = frames[0]
            pi, pos, rows, lens, ok = fr.pi, fr.pos, fr.rows, fr.lens, oks[0]
        else:
            pi = np.concatenate([fr.pi for fr in frames])
            pos = np.concatenate([fr.pos for fr in frames])
            rows = np.concatenate([fr.rows for fr in frames])
            lens = np.concatenate([fr.lens for fr in frames])
            ok = np.concatenate(oks)
        si = np.repeat([fr.si for fr in frames], sizes)
        fno = np.repeat(np.arange(base, base + len(frames)), sizes)
        sidx = np.repeat([fr.shard_index for fr in frames], sizes)
        good = np.flatnonzero(ok)
        if good.size:
            g_rows, g_pi = rows[good], pi[good]
            want = self.shard_len[g_rows, g_pi]
            unset = want == 0
            if unset.any():
                # reversed: the first arrival's length stands
                first = good[unset][::-1]
                self.shard_len[rows[first], pi[first]] = lens[first]
                want = self.shard_len[g_rows, g_pi]
            agree = lens[good] == want
            if not agree.all():
                ok = ok.copy()
                ok[good[~agree]] = False
                good = good[agree]
        if good.size != ok.size:
            burned = np.flatnonzero(~ok)
            np.subtract.at(self.echo_pot, (rows[burned], pi[burned]), 1)
            if good.size == 0:
                return
        g_rows, g_pi, g_si = rows[good], pi[good], si[good]
        np.add.at(self.echo_ok, (g_rows, g_pi), 1)
        self.ver_row[g_si, g_pi] = g_rows
        self.src_frame[g_si, g_pi] = fno[good]
        self.src_pos[g_si, g_pi] = pos[good]
        self.src_sidx[g_si, g_pi] = sidx[good]
        self.verdict_touched[g_pi] = True
        full = self.echo_ok[g_rows, g_pi] >= self.n - self.f
        if full.any():
            self.quorum_row[g_pi[full]] = g_rows[full]

    def decode_shards(self, index: int, root: bytes, k: int):
        """``(idxs, shards)`` of a staged decode — the ``k`` lowest
        distinct shard indices among (root, instance)'s verified
        echoes and their shard bytes, gathered from the retained
        frames — or None while fewer than ``k`` verified."""
        row = self._root_rows.get(root)
        if row is None or self.echo_ok[row, index] < k:
            return None
        senders = np.flatnonzero(self.ver_row[:, index] == row)
        uniq, first = np.unique(
            self.src_sidx[senders, index], return_index=True
        )
        if uniq.size < k:
            return None
        frames = self.frames
        src_frame = self.src_frame[:, index]
        src_pos = self.src_pos[:, index]
        shards = [
            frames[src_frame[s]].shards[src_pos[s]]
            for s in senders[first[:k]].tolist()
        ]
        return tuple(uniq[:k].tolist()), shards


__all__ = ["EchoBank", "EchoFrame", "MAX_SHARD_BYTES", "tree_depth"]
