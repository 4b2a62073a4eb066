"""CryptoHub: columnar wave-batched crypto for the live protocol path.

The reference's cost model is N^2 ECHO-phase Merkle verifications and
~4N^2 threshold-share verifications per epoch (reference
docs/HONEYBADGER-EN.md:93-96), arriving one message at a time.  The
hub is the per-epoch accumulation buffer SURVEY.md §7 (hard part 3)
calls for: protocol instances never run device crypto directly on the
message path — they park work (unverified ECHO branches, undecoded
roots, unverified threshold shares) in their own state and the hub
pulls and executes it in BATCHED dispatches when some instance's
quorum threshold makes results necessary.

Wave-columnar execution (the Thetacrypt "threshold crypto as a
service with request coalescing" shape, PAPERS.md 2502.03247): the
transport's idle callback is the only flush trigger on both
transports, and one flush drains EVERY dirty client of the wave into
a handful of wide typed columns — all pending ECHO-branch proofs,
all ready RS decode-rechecks, all pooled coin/TPKE shares, and then
every share set those verdicts made combinable — and executes ONE
batch call per work kind, in dependency order
(branches -> decodes -> shares -> combines), fanning results back out
via the client callback protocol.  Branch verdicts can unlock decodes
(verified shards complete a staged matrix); the hub re-drains
verdict-marked clients *within the same wave round* so those decodes
ride the round's single decode dispatch instead of a follow-on one.
Share verdicts complete pools: the round's drained clients are asked
(offer_combines) for the f+1 verified shares of every pool that is
now ready, and ALL of them — every coin the round reveals, every
CP-verified decryption, every validator's on a shared hub — are
Lagrange-combined in one exponentiation dispatch before any client's
quorum logic runs.

Why pull, not push: the work lives where the protocol state lives, so
an instance that becomes irrelevant mid-flight (delivered, halted,
epoch GC'd) simply stops offering work — no queue invalidation.  And
because EVERY dirty client's pending work drains whenever ANY client
needs a flush, one instance reaching quorum amortizes the whole
node's backlog into the same dispatch: under 'tpu', an epoch's N
instances' ECHO proofs verify in ~1 `verify_batch` call instead of
N^2 singleton calls, and all TPKE + coin shares fold into ONE
dual-exponentiation dispatch via tpke.verify_share_groups.

Client protocol (duck-typed; see RBC/BBA/HoneyBadger):

  hub.mark_dirty(client)
      REQUIRED whenever pending crypto work appears or becomes
      unblocked (parked branch, staged decode, pooled share); a flush
      round drains only dirty clients
  drain_pending(wave: HubWave) -> None
      move pending work out of client state into the wave's typed
      columns (wave.add_branch_frame / add_branch / add_decode /
      add_share); a client may be drained more than once per round
      and must only offer each work item once
  offer_combines(wave: HubWave) -> None        (optional)
      called on every client drained this round, in drain order, once
      the round's verdicts are in and before any after_crypto_flush:
      offer each share set that is now combinable (wave.add_combine);
      the item callback stores the combined value
  after_crypto_flush() -> None
      verdicts and combined values have been applied via item
      callbacks; run quorum logic

Work item shapes (the wave's typed columns):
  branches: add_branch_frame(frame) — one sender's surviving ECHO
            items of one payload, WHOLE (protocol.echobank.EchoFrame:
            the payload's roots / branches / shards tuples by
            reference, the kept positions, the shard index); verdicts
            return to the frame's bank as
            bank.on_branch_verdicts(frames, oks) — one boolean array a
            frame, one call a bank a dispatch — and then each instance
            noted at drain time (wave.note_branch_client) gets
            after_branch_verdicts(), in drain order.  Duplicate work
            across receivers dedups AT APPEND TIME by object identity
            (dedup mode): an in-proc cluster's N receivers share one
            decoded payload's tuples, so ONE probe a frame (the ids of
            the tuples and the shard index) finds the payload's slots,
            and the content-key memo is consulted once per distinct
            check, not once per (check, receiver).
            add_branch(client, root: bytes32, leaf: bytes,
            branch: tuple[bytes32,...], index: int, ctx) is the
            per-item entry of the same column (verdicts in bulk via
            client.on_branch_verdicts(ctxs, oks)); its slots dedup by
            the ids of the item's own objects, as a width-1 frame's do.
  decodes:  add_decode(root: bytes32, idxs: tuple[int,...],
            shards: list[bytes] (k branch-verified shards, idxs
            order), cb(data: Optional[ndarray])) — decode + re-encode
            + Merkle-root recheck (docs/RBC-EN.md:37-39) batched
            across instances; the hub builds each unique matrix once.
  shares:   add_share(pub, base: int, context: bytes,
            senders: list[str], shares: list[DhShare],
            cb(senders, verdicts: list[bool]))
  combines: add_combine(shares: list[DhShare] (>= threshold,
            index-distinct), threshold: int, group: GroupParams,
            cb(value: int)) — the first ``threshold`` shares by
            Shamir index combine to base^s; one
            tpke.combine_share_wave per flush round over every
            offered set (thresholds may differ set to set; one wave
            per GroupParams), a wave of COMBINE_CHUNK_ROWS rows a
            call, routed host kernel or device by the engine's floor.
            Counted in combine_batches / combine_items /
            combine_memo_hits, not in ``dispatches``.

The settler's side of the combine column (it runs before the flush,
at the head of the idle phase, and needs its values within its pass):

  hub.note_combine_source(owner)
      at wave time: a message wave pooled decryption shares at owner
  hub.take_combines(owner, wants) -> list[int]
      wants: [(meta, shares, threshold, group)]; the first taker of an
      idle phase combines its wants together with every other noted
      owner's (owner.settle_combine_wants() -> the same rows), whose
      values park until their own pass takes them
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from cleisthenes_tpu.ops.backend import BatchCrypto
from cleisthenes_tpu.ops.coin import share_batch as coin_share_batch
from cleisthenes_tpu.ops.tpke import (
    combine_share_wave,
    issue_shares_batch,
    verify_share_groups,
)
from cleisthenes_tpu.utils import trace
from cleisthenes_tpu.utils.determinism import guarded_by
from cleisthenes_tpu.utils.lockcheck import new_lock
from cleisthenes_tpu.utils.memo import BoundedFifoMemo

# A flush settles in 1-2 wave rounds (branch verdicts unlock decodes
# WITHIN a round; only share burns and quorum follow-ons need another);
# the cap only guards against a pathological client that re-offers
# work forever.
MAX_FLUSH_ROUNDS = 64

# Verdict-memo capacities.  Primary eviction is epoch GC (drop_scope
# clears the memos — every key belongs to some epoch's traffic, and
# stale entries never pay their rent back); the caps are a second
# bound for pathological single-epoch volume, sized per entry weight:
# share keys are a few hundred bytes (big-int triples), branch keys
# carry a leaf + branch path (~KB), decode keys are (root, idxs).
SHARE_MEMO_CAP = 1 << 16
BRANCH_MEMO_CAP = 1 << 15
DECODE_MEMO_CAP = 1 << 10

# wave-width samples kept for bench percentiles (protocol sections
# report wave_width_p50/p95); a run's flushes far exceed this only in
# pathological schedules, and old samples are as good as new ones
WAVE_WIDTH_CAP = 1 << 16


# Bounded memo with FIFO eviction (never clear-all): the ONE shared
# discipline, hoisted to utils.memo so the transport plane's frame-
# decode memo evicts identically without importing protocol code.
# The historical name is kept — hub call sites and the tx-parse memo
# (protocol/honeybadger.py) import it from here.
_Memo = BoundedFifoMemo


def _park(park: Dict, meta, shares, value: int) -> None:
    park[meta] = (shares, value)


class HubWave:
    """One flush's typed work columns.

    Branch work is slotted: ``b_slots`` is the unique-work list, a
    frame lands as (frame, slot array) and a per-item append as a
    (client, ctx, slot) row.  In dedup mode (cluster-shared hub)
    uniqueness is established at APPEND time by object identity — the
    in-proc transport's payload memo hands every receiver the same
    payload tuples, so one probe a frame (the ids of its roots /
    branches / shards tuples and the shard index) finds the slot of
    every position the payload already has, and a wave's N copies of
    one check collapse to a single slot without hashing any content.
    A width-1 frame is keyed like a per-item append, by the ids of the
    item's own root / leaf / branch objects: the router wraps a scalar
    ECHO's fields in fresh tuples a delivery.  Ids are only compared
    between live objects held by this wave (the columns pin them), so
    reuse-after-GC cannot alias.  Decode, share and combine items stay
    flat lists — their populations are ~N per wave per node, not ~N^2.
    """

    __slots__ = (
        "dedup",
        "b_slots",
        "b_items",
        "b_frames",
        "b_frame_slots",
        "b_frame_items",
        "b_clients",
        "_b_ids",
        "_f_ids",
        "decodes",
        "shares",
        "combines",
        "clients",
    )

    def __init__(self, dedup: bool) -> None:
        self.dedup = dedup
        self.b_slots: List[Tuple] = []  # unique (root, leaf, branch, idx)
        self.b_items: List[Tuple] = []  # (client, ctx, slot)
        self.b_frames: List[object] = []  # EchoFrame, arrival order
        self.b_frame_slots: List[np.ndarray] = []  # slot of each kept item
        self.b_frame_items = 0
        # instances whose parked items a frame carries, in drain order
        self.b_clients: List[object] = []
        self._b_ids: Dict[Tuple, int] = {}
        # (id(roots), id(branches), id(shards), index) -> slot of each
        # payload position (-1 = none yet)
        self._f_ids: Dict[Tuple, np.ndarray] = {}
        self.decodes: List[Tuple] = []  # (root, idxs, [shards], cb, n)
        self.shares: List[Tuple] = []  # (pub, base, ctx, senders, shs, cb)
        self.combines: List[Tuple] = []  # (shares, threshold, group, cb)
        self.clients: List[object] = []  # drained clients, arrival order

    def add_branch(
        self, client, root: bytes, leaf: bytes, branch: tuple,
        index: int, ctx,
    ) -> None:
        slots = self.b_slots
        if self.dedup:
            key = (id(root), id(leaf), id(branch), index)
            slot = self._b_ids.get(key)
            if slot is None:
                slot = len(slots)
                self._b_ids[key] = slot
                slots.append((root, leaf, branch, index))
        else:
            slot = len(slots)
            slots.append((root, leaf, branch, index))
        self.b_items.append((client, ctx, slot))

    def add_branch_frame(self, frame) -> None:
        """One parked ECHO frame, whole: its kept items' slots are
        found (dedup mode) by one identity probe of the payload, made
        for the positions no receiver has offered yet, or (a hub a
        node) are the items themselves."""
        pos = frame.pos
        if pos.size == 0:
            return
        slots = self.b_slots
        roots, branches, shards = frame.roots, frame.branches, frame.shards
        index = frame.shard_index
        if not self.dedup:
            base = len(slots)
            slots.extend(
                [(roots[k], shards[k], branches[k], index) for k in pos.tolist()]
            )
            fslots = np.arange(base, base + pos.size)
        elif len(roots) == 1:
            key = (id(roots[0]), id(shards[0]), id(branches[0]), index)
            slot = self._b_ids.get(key)
            if slot is None:
                slot = self._b_ids[key] = len(slots)
                slots.append((roots[0], shards[0], branches[0], index))
            fslots = np.full(1, slot, dtype=np.int64)
        else:
            key = (id(roots), id(branches), id(shards), index)
            known = self._f_ids.get(key)
            if known is None:
                known = self._f_ids[key] = np.full(
                    len(roots), -1, dtype=np.int64
                )
            fslots = known[pos]
            if fslots.min() < 0:
                for k in pos[fslots < 0].tolist():
                    known[k] = len(slots)
                    slots.append((roots[k], shards[k], branches[k], index))
                fslots = known[pos]
        self.b_frames.append(frame)
        self.b_frame_slots.append(fslots)
        self.b_frame_items += pos.size

    def note_branch_client(self, client) -> None:
        """``client`` (an RBC instance) had parked items in the frames
        just offered: its ``after_branch_verdicts()`` runs once the
        verdicts have landed on its bank, in the order noted."""
        self.b_clients.append(client)

    def add_decode(
        self, root: bytes, idxs: tuple, shards: list, cb, n=None
    ) -> None:
        # ``n`` is the requesting instance's roster width (dynamic
        # membership: epochs under different roster versions carry
        # different RS geometries; None = the hub's native width)
        self.decodes.append((root, idxs, shards, cb, n))

    def add_share(
        self, pub, base: int, context: bytes, senders: list, shares: list,
        cb,
    ) -> None:
        self.shares.append((pub, base, context, senders, shares, cb))

    def add_combine(self, shares: list, threshold: int, group, cb) -> None:
        self.combines.append((shares, threshold, group, cb))

    def has_work(self) -> bool:
        return bool(
            self.b_items
            or self.b_frames
            or self.decodes
            or self.shares
            or self.combines
        )

    def branch_items(self) -> int:
        return len(self.b_items) + self.b_frame_items

    def take_branches(self) -> Tuple[List, List, List, List, List]:
        """(slots, per-item rows, frames, frame slot arrays, noted
        clients) — and an empty column."""
        out = (
            self.b_slots, self.b_items, self.b_frames,
            self.b_frame_slots, self.b_clients,
        )
        self.b_slots, self.b_items, self.b_frames = [], [], []
        self.b_frame_slots, self.b_clients = [], []
        self.b_frame_items = 0
        self._b_ids = {}
        self._f_ids = {}
        return out

    def take_decodes(self) -> List[Tuple]:
        out, self.decodes = self.decodes, []
        return out

    def take_shares(self) -> List[Tuple]:
        out, self.shares = self.shares, []
        return out

    def take_combines(self) -> List[Tuple]:
        out, self.combines = self.combines, []
        return out


@guarded_by("_dec_lock", "_dec_pool", "_dec_results")
class CryptoHub:
    """Per-node batched-crypto service shared by all protocol instances.

    ``dedup=True`` (the cluster-shared simulation mode) memoizes
    verification VERDICTS across clients: a coin/TPKE CP check, an
    ECHO-branch Merkle proof, or an RS decode-recheck is a pure
    function of its math inputs, and in an N-node in-proc simulation
    every node receives — and would redundantly re-verify — the same
    N^2 shares and branches.  The memo executes each distinct check
    once and fans the verdict out, which is exactly what the N real
    hosts of a deployed cluster do in parallel wall-clock: per-node
    work stays honest, only the single-process serialization artifact
    (N x the same pure computation, run serially) is removed.  Memo
    keys bind every input the verdict depends on (group, public-key
    identity, base, context, share values / root, leaf, branch,
    index); decode keys bind (root, idxs) — sufficient because only
    BRANCH-VERIFIED shards ever reach a decode request, and two
    different shard byte-strings verifying at the same index under
    the same root would be a SHA-256 second preimage.  Per-node hubs
    in a real deployment leave dedup off: nothing repeats.
    """

    def __init__(self, crypto: BatchCrypto, dedup: bool = False):
        self.crypto = crypto
        self.dedup = dedup
        # (n, k) -> BatchCrypto for decode groups whose RS geometry
        # differs from the native one (dynamic membership: epochs
        # under a resized roster version)
        self._crypto_cache: Dict[Tuple[int, int], BatchCrypto] = {}
        if dedup:
            self._share_memo = _Memo(SHARE_MEMO_CAP)
            self._branch_memo = _Memo(BRANCH_MEMO_CAP)
            self._decode_memo = _Memo(DECODE_MEMO_CAP)
            # id(pub) -> (pub, token): small ints stand in for the
            # (expensive-to-hash) public-key objects in memo keys; the
            # held reference pins the id against reuse
            self._pub_tokens: Dict[int, Tuple[object, int]] = {}
        # scope (epoch int, or any hashable) -> clients; scopes drop
        # wholesale when HoneyBadger GCs an epoch
        self._clients: Dict[object, List[object]] = {}
        # Clients with (possibly) pending work: every state change
        # that creates or unblocks crypto work calls mark_dirty, and a
        # flush round drains ONLY dirty clients — at N validators x N
        # instances, polling every registered client every round was a
        # top-5 epoch cost.  A client that stages work without marking
        # itself dirty will stall: marking is part of the client
        # protocol (see module docstring).
        # An insertion-ordered dict-as-set, NOT a set: drain order
        # decides the order work items batch and verdict callbacks
        # fire, which decides outbound payload order — id()-hash set
        # order would let two runs of the same seeded schedule ship
        # waves in different orders (staticcheck DET002).
        self._dirty: Dict[object, None] = {}
        self._flushing = False
        # Deferred mode (HoneyBadger.transport_manages_idle sets
        # ``hub.defer = True`` when its transport promises an idle
        # callback): request_flush only records the want; the actual
        # flush runs at the transport's quiescence point — the ONLY
        # flush trigger on both transports — so one flush absorbs the
        # whole message wave's pending work instead of firing per
        # quorum event.
        self.defer = False
        self.flush_wanted = False
        # observability (utils.metrics reads these)
        self.flushes = 0
        self.branch_items = 0
        # frames the branch column took whole, and the distinct checks
        # (slots) their items and the per-item appends came to
        self.branch_frames = 0
        self.branch_slots = 0
        self.decode_items = 0
        self.share_items = 0
        self.dispatches = 0
        # Wave-batched coin-issue column (ISSUE 13): owners park
        # (secret, base, context, vk) issue items at aux-quorum time
        # (stage_coin_issue) and collect the shares at their own drain
        # point (take_coin_issues).  The FIRST taker of a wave executes
        # EVERY staged owner's pending items in one ops.coin.share_batch
        # dispatch — one native multi-exponentiation and one CP-nonce
        # draw for all BBA instances and rounds the wave touched, across
        # ALL nodes of a shared-hub cluster — and parks each owner's
        # shares until its drain claims them, so each owner still
        # broadcasts at its own drain point, in stage order.  Counter
        # semantics: coin_issue_batches counts native coin-issue
        # dispatches, the number bench.py reports as
        # coin_dispatches_per_epoch and perfgate gates.
        self.coin_issue_batches = 0
        self.coin_issue_items = 0
        self._coin_pool: List[Tuple] = []  # (owner, meta, item, group)
        # owner -> [(meta, share)] awaiting the owner's drain.  A
        # restarted owner object abandons its parked rows (one stale
        # entry per crash — bounded by the run's restart count).
        self._coin_results: Dict[object, List[Tuple]] = {}
        # Eager dec-share issue column (K-deep pipelined frontiers,
        # Config.pipeline_depth > 1): the TPKE twin of the coin
        # column above.  Owners stage (share, base, context, vk)
        # issue items the moment an epoch ORDERS — mid-wave — and
        # collect the DhShares at the turn's piggyback drain
        # (take_dec_issues); the first taker executes the whole
        # staged pool in one ops.tpke.issue_shares_batch dispatch,
        # so a wave that orders epochs on several shared-hub nodes
        # (or K epochs back to back) pays one exponentiation
        # dispatch and one CP-nonce draw, not one per node per epoch.
        self.dec_issue_batches = 0
        self.dec_issue_items = 0
        # guarded: a cluster-SHARED hub serves every node's stage/
        # drain calls, and the ISSUE-17 sweep requires the column's
        # pool+results to move under one declared lock
        self._dec_lock = new_lock()
        self._dec_pool: List[Tuple] = []  # (owner, meta, item, group)
        self._dec_results: Dict[object, List[Tuple]] = {}
        # Combine column (ISSUE 32): every Lagrange combine of the
        # served path — a revealed coin's f+1 verified shares, an
        # optimistic or CP-verified decryption's — runs here, many sets
        # to one tpke.combine_share_wave.  combine_batches counts the
        # exponentiation dispatches that made (items / batches is how
        # widely the column engages: one set a call before it
        # existed); combine_memo_hits the sets answered without one.
        # Like the issue columns' pairs they stay out of
        # ``dispatches``.
        self.combine_batches = 0
        self.combine_items = 0
        self.combine_memo_hits = 0
        # The settler's side of the column (take_combines): owners
        # whose dec-share pools a message wave fed (note_combine_source)
        # -> asked for their ready sets by the first taker of the idle
        # phase; owner -> {meta: (shares, value)} parked until the
        # owner's own settler pass claims them.
        self._comb_sources: Dict[object, None] = {}
        self._comb_results: Dict[object, Dict] = {}
        # per-flush total column width (branch+decode+share items) of
        # every flush that carried work, for the bench's
        # wave_width_p50/p95 counters (bounded; see WAVE_WIDTH_CAP)
        self.wave_widths: List[int] = []
        # flight recorder (utils/trace.py).  Per-node hubs inherit
        # the owner's recorder; a cluster-SHARED hub gets its own
        # "hub" track (its flushes serve the whole roster and belong
        # to no single node's timeline).  None = tracing off.
        self.trace = None

    # -- membership --------------------------------------------------------

    def register(self, scope, client) -> None:
        self._clients.setdefault(scope, []).append(client)

    def mark_dirty(self, client) -> None:
        """Client protocol: call whenever pending crypto work appears
        or becomes unblocked (a parked branch, a staged decode, a
        pooled share).  Idempotent and O(1)."""
        self._dirty[client] = None

    def drop_scope(self, scope) -> None:
        dropped = self._clients.pop(scope, None)
        if dropped:
            for client in dropped:
                self._dirty.pop(client, None)
        if self.dedup:
            # epoch GC is the natural memo eviction point: all of a
            # completed epoch's keys are dead, and any live entry a
            # clear loses costs at most one re-verification
            self._share_memo.map.clear()
            self._branch_memo.map.clear()
            self._decode_memo.map.clear()
            # the memos keyed by these tokens are gone, so a held key
            # object has no remaining value — dropping the table stops
            # unbounded growth under epoch re-keying
            self._pub_tokens.clear()

    # -- flushing ----------------------------------------------------------

    def request_flush(self) -> None:
        """Run a flush now — unless one is already running (its wave
        loop will pick the new work up) or deferred mode parks the
        request for the transport's idle callback."""
        if self._flushing:
            return
        if self.defer:
            self.flush_wanted = True
            return
        self.flush()

    def run_deferred(self) -> None:
        """Idle-callback entry: run the flush the message wave asked
        for (no-op when nothing requested one)."""
        if self.flush_wanted and not self._flushing:
            self.flush_wanted = False
            self.flush()

    def _drain_dirty(self, wave: HubWave) -> None:
        clients = list(self._dirty)
        self._dirty.clear()
        with trace.span("hub", "drain", clients=len(clients)):
            for c in clients:
                c.drain_pending(wave)
        wave.clients.extend(clients)

    def flush(self) -> None:
        """Drain every dirty client into typed columns and execute one
        batch dispatch per work kind, in dependency order.  Branch
        verdicts that unlock decodes re-mark their client; the
        mid-round re-drain folds those decodes into the SAME round's
        decode dispatch.  The loop iterates only when verdicts create
        genuinely new work (a share burn pulling parked replacements,
        quorum logic staging follow-ons); it terminates when a round
        neither executed work nor left dirty clients."""
        if self._flushing:
            return
        self._flushing = True
        self.flush_wanted = False  # any full flush satisfies the want
        self.flushes += 1
        with trace.span("hub", "flush", recorder=self.trace) as sp:
            self._flush_waves(sp)

    def _flush_waves(self, sp) -> None:
        d0, b0, k0, s0 = (
            self.dispatches,
            self.branch_items,
            self.decode_items,
            self.share_items,
        )
        rounds = 0
        try:
            wave = HubWave(self.dedup)
            for _ in range(MAX_FLUSH_ROUNDS):
                if self._dirty:
                    self._drain_dirty(wave)
                if not wave.has_work():
                    break
                rounds += 1
                if wave.b_items or wave.b_frames:
                    with trace.span(
                        "hub", "branches", items=wave.branch_items(),
                        frames=len(wave.b_frames), slots=len(wave.b_slots),
                    ):
                        self._run_branches(*wave.take_branches())
                    if self._dirty:
                        # verdicts unlocked work (a completed decode
                        # matrix): drain it into THIS round's columns
                        self._drain_dirty(wave)
                if wave.decodes:
                    with trace.span(
                        "hub", "decodes", items=len(wave.decodes)
                    ):
                        self._run_decodes(wave.take_decodes())
                if wave.shares:
                    with trace.span(
                        "hub", "shares", items=len(wave.shares)
                    ):
                        self._run_shares(wave.take_shares())
                # executor callbacks may re-mark clients (e.g. a share
                # burn with parked replacements); quorum logic runs on
                # every client drained this round, in drain order —
                # after the round's verdicts have shown which of them
                # hold a combinable share set, and those sets have
                # been combined together
                clients, wave.clients = wave.clients, []
                order = list(dict.fromkeys(clients))
                with trace.span("hub", "combines") as csp:
                    for c in order:
                        offer = getattr(c, "offer_combines", None)
                        if offer is not None:
                            offer(wave)
                    if wave.combines:
                        self._run_combines(wave.take_combines(), csp)
                with trace.span("hub", "callbacks", clients=len(clients)):
                    for c in order:
                        c.after_crypto_flush()
        finally:
            self._flushing = False
            width = (
                (self.branch_items - b0)
                + (self.decode_items - k0)
                + (self.share_items - s0)
            )
            if width and len(self.wave_widths) < WAVE_WIDTH_CAP:
                self.wave_widths.append(width)
            sp.note(
                dispatches=self.dispatches - d0,
                branches=self.branch_items - b0,
                decodes=self.decode_items - k0,
                shares=self.share_items - s0,
                wave_width=width,
                rounds=rounds,
            )

    # -- executors ---------------------------------------------------------

    def _run_branches(
        self,
        slots: List[Tuple],
        items: List[Tuple],
        frames: List = (),
        frame_slots: List = (),
        clients: List = (),
    ) -> None:
        """Branch proofs grouped by (depth, leaf length) — one
        merkle.verify_batch per group (trees of one roster share a
        depth, so this is ~one group per wave).  Content-key memo
        lookups run per unique SLOT (the wave already id-deduped the
        N-receiver copies), and verdicts deliver in BULK: a frame's as
        one boolean array to its bank (``on_branch_verdicts(frames,
        oks)``, one call a bank), per-item appends' per client
        (``on_branch_verdicts(ctxs, oks)``); then every noted instance
        runs ``after_branch_verdicts()``, in drain order."""
        self.branch_items += len(items) + sum(
            fs.size for fs in frame_slots
        )
        self.branch_frames += len(frames)
        self.branch_slots += len(slots)
        verdicts: List[bool] = [False] * len(slots)
        if self.dedup:
            memo = self._branch_memo.map
            fresh: List[Tuple] = []
            for si, (root, leaf, branch, index) in enumerate(slots):
                key = (root, leaf, branch, index)
                hit = memo.get(key)
                if hit is None:
                    fresh.append((root, leaf, branch, index, si, key))
                else:
                    verdicts[si] = hit
            if fresh:
                put = self._branch_memo.put

                def fill(it, good, local=verdicts, put=put):
                    local[it[4]] = good
                    put(it[5], good)

                self._verify_branch_groups(fresh, fill)
        elif slots:
            self._verify_branch_groups(
                [
                    slot + (si, None)
                    for si, slot in enumerate(slots)
                ],
                lambda it, good: verdicts.__setitem__(it[4], good),
            )
        # bulk delivery, preserving per-client arrival order
        by_client: Dict[int, Tuple[object, List, List]] = {}
        for client, ctx, slot in items:
            ent = by_client.get(id(client))
            if ent is None:
                ent = (client, [], [])
                by_client[id(client)] = ent
            ent[1].append(ctx)
            ent[2].append(verdicts[slot])
        for client, ctxs, oks in by_client.values():
            client.on_branch_verdicts(ctxs, oks)
        if frames:
            good = np.asarray(verdicts, dtype=bool)
            by_bank: Dict[int, Tuple[object, List, List]] = {}
            for frame, fslots in zip(frames, frame_slots):
                ent = by_bank.get(id(frame.bank))
                if ent is None:
                    ent = by_bank[id(frame.bank)] = (frame.bank, [], [])
                ent[1].append(frame)
                ent[2].append(good[fslots])
            for bank, bank_frames, oks in by_bank.values():
                bank.on_branch_verdicts(bank_frames, oks)
        for client in clients:
            client.after_branch_verdicts()

    def _verify_branch_groups(
        self, items: List[Tuple], deliver: Callable
    ) -> None:
        groups: Dict[Tuple[int, int], List[Tuple]] = {}
        for item in items:
            _root, leaf, branch = item[0], item[1], item[2]
            groups.setdefault((len(branch), len(leaf)), []).append(item)
        for group in groups.values():
            self.dispatches += 1
            b = len(group)
            leaf_len = len(group[0][1])
            # single join+frombuffer per column: per-item np.stack /
            # frombuffer assembly was ~5% of an N=64 epoch
            roots = np.frombuffer(
                b"".join(it[0] for it in group), dtype=np.uint8
            ).reshape(b, 32)
            leaves = np.frombuffer(
                b"".join(it[1] for it in group), dtype=np.uint8
            ).reshape(b, leaf_len)
            depth = len(group[0][2])
            if depth:
                branches_arr = np.frombuffer(
                    b"".join(s for it in group for s in it[2]),
                    dtype=np.uint8,
                ).reshape(b, depth, 32)
            else:  # single-leaf trees
                branches_arr = np.zeros((b, 0, 32), dtype=np.uint8)
            indices = np.asarray([it[3] for it in group])
            ok = self.crypto.merkle.verify_batch(
                roots, leaves, branches_arr, indices
            )
            for it, good in zip(group, ok):
                deliver(it, bool(good))

    def _run_decodes(self, items: List[Tuple]) -> None:
        """Interpolate + re-encode + root recheck (docs/RBC-EN.md:37-39)
        for many instances at once, grouped by shard shape — ONE fused
        dispatch per group on the 'tpu' backend
        (BatchCrypto.decode_recheck_batch).  Item shape:
        (root, idxs, [shard bytes], cb); the hub builds each unique
        matrix exactly once (dedup key (root, idxs): decode inputs are
        branch-verified, see class docstring)."""
        self.decode_items += len(items)
        if self.dedup:
            memo = self._decode_memo.map
            local: Dict[Tuple, object] = {}
            _miss = object()
            fresh: List[Tuple] = []
            keys = []
            for root, idxs, shards, _cb, n in items:
                key = (root, idxs)
                keys.append(key)
                if key not in local:
                    hit = memo.get(key, _miss)
                    if hit is _miss:
                        fresh.append((root, idxs, shards, key, n))
                        local[key] = None  # filled by decode below
                    else:
                        local[key] = hit
            if fresh:

                def fill(it, row, local=local):
                    local[it[3]] = row
                    self._decode_memo.put(it[3], row)

                self._decode_groups(fresh, fill)
            for item, key in zip(items, keys):
                row = local[key]
                # hand each client its own copy: decoded rows feed
                # straight into batch deserialization and must not
                # alias across nodes
                item[3](None if row is None else row.copy())
            return
        self._decode_groups(items, lambda it, row: it[3](row))

    def _decode_groups(self, items: List[Tuple], deliver: Callable) -> None:
        # grouped by (roster width, k, shard length): epochs under
        # different roster versions (dynamic membership) carry
        # different RS geometries and must not share a coder dispatch
        groups: Dict[Tuple[int, int, int], List[Tuple]] = {}
        for item in items:
            idxs, shards = item[1], item[2]
            n = item[4] if len(item) > 4 else None
            groups.setdefault(
                (n, len(idxs), len(shards[0])), []
            ).append(item)
        for (n, _k, _length), group in groups.items():
            k, length = len(group[0][1]), len(group[0][2][0])
            idx_arr = np.asarray([it[1] for it in group])
            # one join+frombuffer for the whole group's matrices (the
            # per-client np.stack of per-shard frombuffers was ~3% of
            # an N=64 epoch)
            shard_arr = np.frombuffer(
                b"".join(s for it in group for s in it[2]),
                dtype=np.uint8,
            ).reshape(len(group), k, length)
            data, roots, dispatches = self._crypto_for(
                n, k
            ).decode_recheck_batch(idx_arr, shard_arr)
            self.dispatches += dispatches
            for it, row, root in zip(group, data, roots):
                deliver(it, row if root.tobytes() == it[0] else None)

    def _crypto_for(self, n, k):
        """The BatchCrypto whose erasure geometry matches one decode
        group: the hub's native one when (n, k) agree (every request
        before a reconfig, and all of them on fixed rosters), else a
        cached per-geometry sibling on the same backend."""
        c = self.crypto
        if n is None or (n == c.n and k == c.k):
            return c
        hit = self._crypto_cache.get((n, k))
        if hit is None:
            hit = BatchCrypto(
                c.backend, n, (n - k) // 2, k,
                mesh_shape=c.mesh_shape,
            )
            self._crypto_cache[(n, k)] = hit
        return hit

    def _run_shares(self, items: List[Tuple]) -> None:
        """ALL pooled threshold shares (TPKE decryption + BBA coins,
        every instance) in ONE dual-exponentiation dispatch."""
        self.share_items += sum(len(it[4]) for it in items)
        if self.dedup:
            self._run_shares_dedup(items)
            return
        self.dispatches += 1
        verdicts = verify_share_groups(
            [(pub, base, shs, ctx) for pub, base, ctx, _snd, shs, _cb in items],
            backend=self.crypto.engine_backend,
            mesh=self.crypto.mesh,
        )
        for item, ok in zip(items, verdicts):
            item[5](item[3], ok)

    def _pub_token(self, pub) -> int:
        ent = self._pub_tokens.get(id(pub))
        if ent is None or ent[0] is not pub:
            ent = (pub, len(self._pub_tokens))
            self._pub_tokens[id(pub)] = ent
        return ent[1]

    def _run_shares_dedup(self, items: List[Tuple]) -> None:
        """Each distinct (pub, base, context, share) CP check verifies
        once; verdicts fan out to every client that pooled a copy."""
        memo = self._share_memo.map
        # local verdict view for THIS call: immune to memo eviction
        # racing between put and the fan-out read below
        local: Dict[Tuple, bool] = {}
        # (token, base, context) -> [(key, share)] of fresh checks
        fresh: Dict[Tuple, List[Tuple]] = {}
        fresh_groups: Dict[Tuple, Tuple] = {}
        item_keys: List[List[Tuple]] = []
        for pub, base, context, _snd, shares, _cb in items:
            tok = self._pub_token(pub)
            gkey = (tok, base, context)
            keys = []
            for sh in shares:
                key = (tok, base, context, sh.index, sh.d, sh.e, sh.z)
                keys.append(key)
                if key not in local:
                    hit = memo.get(key)
                    if hit is None:
                        fresh.setdefault(gkey, []).append((key, sh))
                        fresh_groups[gkey] = (pub, base, context)
                        local[key] = False  # placeholder, filled below
                    else:
                        local[key] = hit
            item_keys.append(keys)
        if fresh:
            self.dispatches += 1
            groups = []
            order = []
            for gkey, pairs in fresh.items():
                pub, base, context = fresh_groups[gkey]
                groups.append((pub, base, [sh for _k, sh in pairs], context))
                order.append(pairs)
            verdicts = verify_share_groups(
                groups,
                backend=self.crypto.engine_backend,
                mesh=self.crypto.mesh,
            )
            put = self._share_memo.put
            for pairs, oks in zip(order, verdicts):
                for (key, _sh), good in zip(pairs, oks):
                    local[key] = good
                    put(key, good)
        for (item, keys) in zip(items, item_keys):
            item[5](item[3], [local[k] for k in keys])

    # -- combine column ------------------------------------------------------

    def _run_combines(self, items: List[Tuple], sp=None) -> None:
        """EVERY offered share set Lagrange-combined in one
        ``tpke.combine_share_wave`` (thresholds may differ from set to
        set; one wave per GroupParams, which only a re-keyed roster
        makes more than one), through the engine the other waves use,
        so its host floor sends a wave of N=16's 6-row sets to the
        threaded host kernel and a roster's worth of 22-row sets at
        N=64 to the device.  Item shape: ``(shares, threshold, group,
        cb(value: int))``."""
        self.combine_items += len(items)
        by_group: Dict[object, List[int]] = {}
        for i, item in enumerate(items):
            by_group.setdefault(item[2], []).append(i)
        if sp:
            sp.note(
                items=len(items),
                rows=sum(item[1] for item in items),
            )
        for group, idxs in by_group.items():
            vals, batches, hits = combine_share_wave(
                [items[i][0] for i in idxs],
                [items[i][1] for i in idxs],
                group,
                backend=self.crypto.engine_backend,
                mesh=self.crypto.mesh,
            )
            self.combine_batches += batches
            self.combine_memo_hits += hits
            for i, val in zip(idxs, vals):
                items[i][3](val)

    def note_combine_source(self, owner) -> None:
        """A message wave pooled decryption shares at ``owner``: its
        next settler pass may find sets to combine.  The first
        ``take_combines`` of the idle phase asks every noted owner
        (``owner.settle_combine_wants()``), so a shared hub folds the
        whole roster's optimistic combines into its one dispatch.
        Idempotent and O(1)."""
        self._comb_sources[owner] = None

    def take_combines(self, owner, wants: List[Tuple]) -> List[int]:
        """The combined values of ``owner``'s ``wants`` — ``(meta,
        shares, threshold, group)`` rows, ``meta`` the owner's own
        hashable handle — in order, within this call.  A want another
        taker's dispatch already combined (same meta, same shares) is
        claimed from the park; otherwise the rest run now, in ONE
        wave with the ready sets of every other noted owner, whose
        values park until their own pass asks (a pass whose pool moved
        in between recombines: the park is keyed by what was
        combined)."""
        self._comb_sources.pop(owner, None)
        parked = self._comb_results.pop(owner, None) or {}
        values: List[int] = [0] * len(wants)
        items: List[Tuple] = []
        for i, (meta, shares, threshold, group) in enumerate(wants):
            hit = parked.get(meta)
            if hit is not None and hit[0] == shares:
                values[i] = hit[1]
            else:
                items.append(
                    (
                        shares, threshold, group,
                        functools.partial(values.__setitem__, i),
                    )
                )
        if items:
            sources, self._comb_sources = self._comb_sources, {}
            for src in sources:
                rows = src.settle_combine_wants()
                if not rows:
                    continue
                park = self._comb_results.setdefault(src, {})
                for meta, shares, threshold, group in rows:
                    items.append(
                        (
                            shares, threshold, group,
                            functools.partial(_park, park, meta, shares),
                        )
                    )
            with trace.span("hub", "combines") as sp:
                self._run_combines(items, sp)
        return values

    # -- coin-issue column --------------------------------------------------

    def stage_coin_issue(self, owner, meta, item, group) -> None:
        """Park one coin-share issue want: ``item`` is the
        ``(secret, base, context, vk)`` tuple ``ops.coin.share_batch``
        takes, ``meta`` the owner's own handle (returned with the
        share), ``group`` the issue's GroupParams.  Staging happens at
        aux-quorum time — during the message wave — so by the first
        drain of the idle phase the whole roster's wants are pooled."""
        self._coin_pool.append((owner, meta, item, group))

    def take_coin_issues(self, owner) -> List[Tuple]:
        """``(meta, share)`` rows for ``owner``, in stage order.  If
        any of the owner's staged items are still pending, the WHOLE
        pool — every staged owner — executes first in one native
        dispatch per distinct group (one group in practice: the coin
        group is deployment-wide), so a wave's coin issues across all
        instances, rounds, and in-proc nodes cost one
        multi-exponentiation and one CP-nonce draw."""
        if any(row[0] is owner for row in self._coin_pool):
            self._run_coin_pool()
        return self._coin_results.pop(owner, [])

    def _run_coin_pool(self) -> None:
        pool, self._coin_pool = self._coin_pool, []

        def tally(n: int) -> None:
            self.coin_issue_batches += 1
            self.coin_issue_items += n

        self._run_owner_pool(
            pool, coin_share_batch, "coin", "share_batch",
            self._coin_results, tally,
        )

    def _run_owner_pool(
        self, pool, kernel, trace_cat, trace_name, results, tally
    ) -> None:
        """The shared discipline of the owner-staged issue columns
        (coin shares and — K-deep eager mode — TPKE dec shares):
        insertion-ordered grouping by group object (DET002: dispatch
        and result order must not depend on hash order), ONE native
        ``kernel`` dispatch per distinct group over the pool's
        ``(secret/share, base, context, vk)`` items, results parked
        per owner in stage order.  ``tally(n_rows)`` bumps the
        column's batch/item counters."""
        groups: Dict[int, List[Tuple]] = {}
        group_objs: Dict[int, object] = {}
        for row in pool:
            gid = id(row[3])
            groups.setdefault(gid, []).append(row)
            group_objs[gid] = row[3]
        for gid, rows in groups.items():
            with trace.span(
                trace_cat, trace_name, recorder=self.trace, n=len(rows)
            ) as sp:
                if sp:
                    sp.note(owners=len({id(row[0]) for row in rows}))
                tally(len(rows))
                shares = kernel(
                    [row[2] for row in rows],
                    group=group_objs[gid],
                    backend=self.crypto.engine_backend,
                    mesh=self.crypto.mesh,
                )
            for row, share in zip(rows, shares):
                results.setdefault(row[0], []).append(
                    (row[1], share)
                )

    # -- dec-share issue column (Config.pipeline_depth > 1) ----------------

    def stage_dec_issue(self, owner, meta, item, group) -> None:
        """Park one TPKE dec-share issue want (the K-deep eager
        piggyback path): ``item`` is the ``(share, base, context,
        vk)`` tuple ``ops.tpke.issue_shares_batch`` takes, ``meta``
        the owner's own handle (returned with the share), ``group``
        the issue's GroupParams.  Staging happens the moment an
        epoch ORDERS — during the message wave — so by the turn's
        piggyback drain every node's (and every freshly ordered
        epoch's) wants are pooled."""
        with self._dec_lock:
            self._dec_pool.append((owner, meta, item, group))

    def take_dec_issues(self, owner) -> List[Tuple]:
        """``(meta, DhShare)`` rows for ``owner``, in stage order.
        If any of the owner's staged items are still pending, the
        WHOLE pool — every staged owner — executes first in one
        native dispatch per distinct group (one in practice: the
        TPKE group is deployment-wide), and each other owner's
        shares park until its own drain claims them, so broadcast
        site and order stay per-node deterministic."""
        with self._dec_lock:
            if any(row[0] is owner for row in self._dec_pool):
                self._run_dec_pool_locked()
            return self._dec_results.pop(owner, [])

    def _run_dec_pool_locked(self) -> None:
        pool, self._dec_pool = self._dec_pool, []

        def tally(n: int) -> None:
            self.dec_issue_batches += 1
            self.dec_issue_items += n

        self._run_owner_pool(
            pool, issue_shares_batch, "settle", "dec_share_batch",
            self._dec_results, tally,
        )

    # -- stats -------------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        return {
            "flushes": self.flushes,
            "dispatches": self.dispatches,
            "branch_items": self.branch_items,
            "branch_frames": self.branch_frames,
            "branch_slots": self.branch_slots,
            "decode_items": self.decode_items,
            "share_items": self.share_items,
            "coin_issue_batches": self.coin_issue_batches,
            "coin_issue_items": self.coin_issue_items,
            "dec_issue_batches": self.dec_issue_batches,
            "dec_issue_items": self.dec_issue_items,
            "combine_batches": self.combine_batches,
            "combine_items": self.combine_items,
            "combine_memo_hits": self.combine_memo_hits,
        }


__all__ = ["CryptoHub", "HubWave"]
