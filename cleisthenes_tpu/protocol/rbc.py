"""RBC: Bracha reliable broadcast with erasure coding + Merkle proofs.

Completes the reference's all-panics skeleton (reference rbc/rbc.go:38-100)
per its own spec (reference docs/RBC-EN.md:28-45):

  propose:  split value into K = N-2f data shards, RS-encode to N
            shards, build a Merkle tree over them, send VAL_j =
            (root h, branch b(j), shard s(j)) to node j
            (rbc/rbc.go:98-100 `shard`; docs/RBC-EN.md:28-33).
  VAL:      (from the proposer only) verify the branch, multicast
            ECHO with the same (h, b(j), s(j)) (docs/RBC-EN.md:34).
  ECHO:     verify branch (rbc/rbc.go:93-95 `validateMessage`); on
            N-f valid ECHOs interpolate from N-2f shards, *recompute
            the root* to catch a Byzantine proposer, then send
            READY(h) (rbc/rbc.go:88-90 `interpolate`;
            docs/RBC-EN.md:35-39).
  READY:    f+1 READY(h) -> send READY(h) if not yet sent; 2f+1
            READY(h) + N-2f verified shards -> decode and deliver
            (docs/RBC-EN.md:41-42).

Crypto never runs on the message path: inbound ECHO proofs park in
the roster-wide ``protocol.echobank.EchoBank`` as frame records (one a
sender's payload) and the decode+root-recheck parks as a request; the
shared ``protocol.hub.CryptoHub`` pulls all pending work — across
every concurrent RBC instance of the epoch — into batched device
dispatches when some instance's quorum threshold makes results
necessary (SURVEY.md §7 hard part 3's per-epoch accumulation buffers;
the reference's N^2-branch-hash cost model is
docs/HONEYBADGER-EN.md:96).  Only the single VAL proof is verified
inline: our own ECHO must go out immediately and nothing else would
trigger a flush that early.

A delivery wave's ECHOes and READYs do not pass through this class an
item at a time: ``EchoBank.wave_echo`` / ``wave_ready`` take the whole
wave as columns, and RBC hears only of threshold crossings
(``_send_ready``, ``_maybe_deliver``, the flush request) and, after a
verdict pass that verified an echo of its instance,
``after_branch_verdicts``.  ``handle_echo_fast`` / ``_echo_item`` are
the per-payload entries (VAL-adjacent ECHOes on a host, rows that
repeat an instance, unit tests); they write the same bank arrays.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Set

from cleisthenes_tpu.config import Config
from cleisthenes_tpu.ops.backend import BatchCrypto
from cleisthenes_tpu.ops.payload import join_payload, split_payload
from cleisthenes_tpu.protocol.echobank import MAX_SHARD_BYTES, EchoBank
from cleisthenes_tpu.transport.message import RbcPayload, RbcType
from cleisthenes_tpu.utils import trace

# id-keyed branch-shape memo: entries hold the branch TUPLE (a few
# hundred bytes — pinning the id against recycling, same discipline as
# the hub's token table) rather than the whole payload, whose shard
# bytes would otherwise keep dead epochs' data resident until the
# wholesale clear at the cap
_BRANCH_SHAPE_MEMO: dict = {}
_BRANCH_SHAPE_MEMO_CAP = 1 << 14


class RBC:
    """One reliable-broadcast instance: (epoch, proposer).

    Mirrors the reference struct (rbc/rbc.go:9-36): n, f, proposer, the
    erasure codec, per-type bookkeeping, and a broadcaster — with the
    request repositories realized as per-root dicts enforcing
    one-vote-per-sender.
    """

    def __init__(
        self,
        *,
        config: Config,
        crypto: BatchCrypto,
        epoch: int,
        proposer: str,
        owner: str,
        member_ids: Sequence[str],
        out,
        hub=None,
        bank=None,
        index=None,
        trace=None,
        metrics=None,
        scope=None,
    ) -> None:
        self.n = config.n
        self.f = config.f
        # READY deliver threshold: 2f+1 baseline, n-f under
        # Config.reduced_quorum (Config.quorum_large)
        self.q_large = config.quorum_large
        self.k = config.data_shards
        self.epoch = epoch
        self.proposer = proposer
        self.owner = owner
        self.members: List[str] = sorted(member_ids)
        if len(self.members) != self.n:
            raise ValueError(
                f"roster size {len(self.members)} != n={self.n}"
            )
        self.crypto = crypto
        self.out = out  # PayloadBroadcaster: broadcast / send_to
        if hub is None:  # standalone use (unit tests): private hub
            from cleisthenes_tpu.protocol.hub import CryptoHub

            hub = CryptoHub(crypto)
        self.hub = hub
        # ECHO/READY receipt state lives in the roster-wide EchoBank
        # (protocol.echobank): ACS shares ONE bank across the epoch's
        # N instances so columnar waves update struct-of-arrays slices;
        # standalone use (unit tests) gets a private single-instance
        # bank — the same arrays, width 1.
        if bank is None:
            bank = EchoBank(
                member_ids, config.f, inst_ids=[proposer], metrics=metrics,
                quorum_large=config.quorum_large,
            )
            index = 0
        self.bank = bank
        self.index = index
        bank.attach(index, self)
        # scope is (owner, epoch): a hub may be SHARED by many
        # in-proc validators (cluster-batched dispatches), and one
        # node advancing epochs must only drop ITS clients.  Lane
        # shard-out (Config.lanes) further qualifies ``scope`` with
        # the lane id — sibling lanes of one node share the hub and
        # run the same epoch numbers concurrently, so epoch GC must
        # be lane-scoped too; at lanes=1, scope == owner.
        self.hub.register((owner if scope is None else scope, epoch), self)
        # flight recorder (None = tracing off; utils/trace.py)
        self.trace = trace
        # owner-node metrics (None in standalone unit tests): the
        # duplicate-vote absorption counter and the per-payload echo
        # counter (echo_items_scalar) are touched here
        self.metrics = metrics

        # hook set by ACS: fn(proposer_id, value_bytes)
        self.on_deliver: Optional[Callable[[str, bytes], None]] = None

        self._member_set = frozenset(self.members)
        self._echo_sent = False
        self._ready_root: Optional[bytes] = None  # root we READY'd
        # One ECHO and one READY per sender per *instance* (a correct
        # node sends exactly one of each; reference rbc/request.go:30-42
        # repositories are keyed by ConnId) — the claim/dedup state
        # lives in the EchoBank's [sender, instance] arrays, which also
        # bound the distinct roots an instance ever counts to n.  The
        # slot is claimed at arrival; a sender whose proof later fails
        # verification has burned its one vote.
        # depth of the padded tree the proposer must have built
        self._depth = bank.depth
        # verified-echo counts, verified shards and the verified shard
        # length of each root live in the bank too, per
        # (root, instance).
        # roots whose decode+recheck is wanted (ready/echo quorum hit)
        self._decode_req: Set[bytes] = set()
        self._bad_roots: Set[bytes] = set()  # failed interpolation recheck
        self._decoded: Dict[bytes, bytes] = {}  # successful decode cache
        self._value: Optional[bytes] = None

    # -- public API (reference rbc/rbc.go:38-76) ---------------------------

    def value(self) -> Optional[bytes]:
        """The delivered value, or None (reference rbc/rbc.go:69-71)."""
        return self._value

    @property
    def delivered(self) -> bool:
        return self._value is not None

    def propose(self, value: bytes) -> None:
        """Shard, build the Merkle tree, send VAL_j to each node j
        (reference rbc/rbc.go:42-44 `broadcast` + :98-100 `shard`)."""
        if self.owner != self.proposer:
            raise ValueError(
                f"{self.owner!r} cannot propose in {self.proposer!r}'s RBC"
            )
        if len(value) > self.k * MAX_SHARD_BYTES - 4 - self.k * 128:
            # shards receivers would reject in _check_proof: fail fast
            raise ValueError(
                f"value of {len(value)} bytes exceeds the "
                f"{self.k} x {MAX_SHARD_BYTES}-byte shard capacity"
            )
        with trace.span(
            "rbc", "propose", recorder=self.trace,
            epoch=self.epoch, bytes=len(value),
        ):
            data = split_payload(value, self.k)
            shards = self.crypto.erasure.encode(data)  # (n, L)
            tree = self.crypto.merkle.build(shards)
            root = tree.root
        for j, member in enumerate(self.members):
            payload = RbcPayload(
                type=RbcType.VAL,
                proposer=self.proposer,
                epoch=self.epoch,
                root_hash=root,
                branch=tuple(tree.branch(j)),
                shard=shards[j].tobytes(),
                shard_index=j,
            )
            self.out.send_to(member, payload)

    def handle_message(self, sender: str, payload: RbcPayload) -> None:
        """Public entry (reference rbc/rbc.go:46-54)."""
        if not isinstance(payload, RbcPayload):
            return
        if self.delivered or sender not in self._member_set:
            return
        if payload.type == RbcType.VAL:
            self._handle_val(sender, payload)
        elif payload.type == RbcType.ECHO:
            self._handle_echo(sender, payload)
        elif payload.type == RbcType.READY:
            self._handle_ready(sender, payload)

    # -- handlers ----------------------------------------------------------

    def _precheck(self, payload: RbcPayload) -> bool:
        return self._precheck_fields(
            payload.root_hash,
            payload.branch,
            payload.shard,
            payload.shard_index,
        )

    def _precheck_fields(
        self, root: bytes, branch: tuple, shard: bytes, shard_index: int
    ) -> bool:
        """Structural validation — everything except the branch hash
        check itself (reference rbc/rbc.go:93-95 `validateMessage`
        minus the crypto, which the hub batches).

        The branch-shape walk memoizes ON OBJECT IDENTITY: the codec's
        payload memo shares one branch tuple across a broadcast's N
        receivers, so the per-sibling length walk runs once per wire
        payload, not once per delivery (the held tuple pins the id);
        the remaining checks are a handful of scalar compares."""
        if not (0 <= shard_index < self.n):
            return False
        if not (0 < len(shard) <= MAX_SHARD_BYTES):
            return False
        if len(root) != 32:
            return False
        if len(branch) != self._depth:
            return False
        ent = _BRANCH_SHAPE_MEMO.get(id(branch))
        if ent is not None and ent[0] is branch:
            ok = ent[1]
        else:
            ok = all(len(b) == 32 for b in branch)
            if len(_BRANCH_SHAPE_MEMO) >= _BRANCH_SHAPE_MEMO_CAP:
                _BRANCH_SHAPE_MEMO.clear()
            _BRANCH_SHAPE_MEMO[id(branch)] = (branch, ok)
        if not ok:
            return False
        # Shards of one root must agree on length (RS needs a matrix).
        # The bank only ever holds BRANCH-VERIFIED lengths (set in
        # _handle_val after _check_proof and by the verdict pass), so
        # an unverified Byzantine ECHO cannot poison the expectation
        # and wedge honest traffic (ADVICE.md round-2 high finding).
        want_len = self.bank.verified_len(self.index, root)
        if want_len and len(shard) != want_len:
            return False
        return True

    def _check_proof(self, payload: RbcPayload) -> bool:
        """Full inline verification (VAL only — ECHO proofs batch
        through the hub).  The one sanctioned direct crypto call in
        protocol/: a single proposer branch per instance, and the ECHO
        reply cannot wait for a wave."""
        if not self._precheck(payload):
            return False
        return self.crypto.merkle.verify_branch(  # staticcheck: allow[DET003] inline VAL check
            payload.root_hash,
            payload.shard,
            list(payload.branch),
            payload.shard_index,
        )

    def _handle_val(self, sender: str, payload: RbcPayload) -> None:
        """docs/RBC-EN.md:34 — echo the received (h, b(j), s(j)) to all.

        Only the proposer may send VAL, and only the first one counts
        (reference rbc/rbc.go:56-58)."""
        if sender != self.proposer or self._echo_sent:
            return
        if not self._check_proof(payload):
            return
        # verified: this length is now the root's authoritative one
        self.bank.set_verified_len(
            self.index, payload.root_hash, len(payload.shard)
        )
        self._echo_sent = True
        if self.trace is not None:
            self.trace.instant(
                "rbc", "val", epoch=self.epoch, proposer=self.proposer
            )
        self.out.broadcast(
            RbcPayload(
                type=RbcType.ECHO,
                proposer=self.proposer,
                epoch=self.epoch,
                root_hash=payload.root_hash,
                branch=payload.branch,
                shard=payload.shard,
                shard_index=payload.shard_index,
            )
        )

    def _handle_echo(self, sender: str, payload: RbcPayload) -> None:
        self.handle_echo_fast(
            sender,
            payload.root_hash,
            payload.branch,
            payload.shard,
            payload.shard_index,
        )

    def handle_echo_fast(
        self,
        sender: str,
        root: bytes,
        branch: tuple,
        shard: bytes,
        shard_index: int,
    ) -> None:
        """docs/RBC-EN.md:35-39 (reference rbc/rbc.go:60-62) — the
        field-level scalar entry; a delivery wave's ECHOes run the
        same filters, precheck and claim as one vectorized pass in
        ``EchoBank.wave_echo`` and never come here."""
        bank = self.bank
        si = bank.sidx.get(sender)
        if si is None:
            return
        if bank.echo_seen[si, self.index]:  # one ECHO per sender
            if self.metrics is not None:
                self.metrics.dedup_absorbed.inc()
            return
        self._echo_item(si, sender, root, branch, shard, shard_index)

    def _echo_item(
        self,
        si: int,
        sender: str,
        root: bytes,
        branch: tuple,
        shard: bytes,
        shard_index: int,
    ) -> None:
        """Claim + park one deduped ECHO (the per-payload twin of
        ``EchoBank.wave_echo``'s pass).  The branch proof is NOT
        verified here: it parks in the bank as a frame of width 1 and
        verifies in the hub's next batched dispatch — triggered below
        the moment this root could reach its N-f quorum."""
        if self.metrics is not None:
            self.metrics.echo_items_scalar.inc()
        if not self._precheck_fields(root, branch, shard, shard_index):
            return
        # slot claimed; burns if the proof later fails verification
        pot = self.bank.echo_park(
            self.index, si, shard_index, root, branch, shard
        )
        self.hub.mark_dirty(self)
        if (
            pot >= self.n - self.f
            and self._ready_root is None
            and root not in self._bad_roots
        ):
            self.hub.request_flush()
        self._maybe_deliver(root)

    def handle_ready_root(self, sender: str, root: bytes) -> None:
        """READY without a payload object (columnar batch path) —
        guards mirror handle_message's."""
        if self.delivered or sender not in self._member_set:
            return
        self._handle_ready_root(sender, root)

    def _handle_ready(self, sender: str, payload: RbcPayload) -> None:
        """docs/RBC-EN.md:41-42 (reference rbc/rbc.go:64-66)."""
        self._handle_ready_root(sender, payload.root_hash)

    def _handle_ready_root(self, sender: str, root: bytes) -> None:
        if len(root) != 32:
            return
        bank = self.bank
        si = bank.sidx.get(sender)
        if si is None:
            return
        cnt = bank.ready_add(self.index, si, root)
        if cnt is None:  # one READY per sender (dedup counted in bank)
            return
        # f+1 READY(h) -> relay READY(h) once (amplification step)
        if cnt >= self.f + 1 and self._ready_root is None:
            self._send_ready(root)
        self._maybe_deliver(root)

    # -- quorum actions ----------------------------------------------------

    def _send_ready(self, root: bytes) -> None:
        self._ready_root = root
        if self.trace is not None:
            # fires at most once per instance (_ready_root gates every
            # caller): the READY quorum-crossing marker
            self.trace.instant(
                "rbc", "ready", epoch=self.epoch, proposer=self.proposer
            )
        self.out.broadcast(
            RbcPayload(
                type=RbcType.READY,
                proposer=self.proposer,
                epoch=self.epoch,
                root_hash=root,
            )
        )

    def _request_decode(self, root: bytes) -> None:
        """Ask the hub for interpolate + re-encode + root recheck
        (docs/RBC-EN.md:37-39) at its next flush."""
        if (
            root in self._decoded
            or root in self._bad_roots
            or root in self._decode_req
        ):
            return
        self._decode_req.add(root)
        if self.trace is not None:
            # the ECHO-quorum crossing: a decode+recheck became wanted
            self.trace.instant(
                "rbc",
                "echo_quorum",
                epoch=self.epoch,
                proposer=self.proposer,
            )
        self.hub.mark_dirty(self)

    def _maybe_deliver(self, root: bytes) -> None:
        """q_large READY(h) + N-2f verified shards -> deliver
        (docs/RBC-EN.md:41-42; q_large = 2f+1 baseline, n-f reduced)."""
        if self.delivered:
            return
        if self.bank.ready_count(self.index, root) < self.q_large:
            return
        value = self._decoded.get(root)
        if value is None:
            # decode (or the shard verifications feeding it) is still
            # pending: stage the request and flush if work exists
            self._request_decode(root)
            if root in self._decode_req or self.bank.has_parked[self.index]:
                self.hub.request_flush()
            if self.delivered:
                return  # the flush's quorum pass delivered already
            value = self._decoded.get(root)
            if value is None:
                return
        self._value = value
        if self.trace is not None:
            self.trace.instant(
                "rbc",
                "deliver",
                epoch=self.epoch,
                proposer=self.proposer,
                bytes=len(value),
            )
        # the instance is terminal now — the bank's sentinel row drops
        # every later vote vectorized
        self._decode_req.clear()
        self.bank.deactivate(self.index)
        if self.on_deliver is not None:
            self.on_deliver(self.proposer, value)

    # -- hub client protocol (protocol.hub.CryptoHub) ----------------------

    def drain_pending(self, wave) -> None:
        """Move pending crypto work into the wave's typed columns
        (protocol.hub.HubWave): the bank's parked ECHO frames into the
        branch column (whole, by the first instance drained), every
        staged decode whose matrix is complete as a decode item (shard
        BYTES in index order — the hub builds each unique matrix once
        instead of one np.stack per client)."""
        bank = self.bank
        parked = bank.has_parked[self.index]
        if self.delivered or not (parked or self._decode_req):
            return  # fast path: the hub may drain a client twice/round
        if parked:
            bank.drain_parked(wave, self)
        # staged decode requests with enough verified shards; sorted:
        # _decode_req is a set of 32-byte roots, and its hash order
        # (PYTHONHASHSEED-dependent) would otherwise decide decode
        # batching and READY emission order across instances
        for root in sorted(self._decode_req):
            if root in self._decoded or root in self._bad_roots:
                self._decode_req.discard(root)
                continue
            got = bank.decode_shards(self.index, root, self.k)
            if got is None:
                continue  # stays staged until shards verify
            self._decode_req.discard(root)
            wave.add_decode(
                root, got[0], got[1], self._make_decode_cb(root), n=self.n
            )

    def after_branch_verdicts(self) -> None:
        """The hub's branch verdicts have landed on the bank's arrays
        (``EchoBank.on_branch_verdicts``); called once a drained
        instance, in drain order.  A root that crossed its N-f echo
        quorum stages its decode request IMMEDIATELY (not in
        after_crypto_flush): the hub re-drains verdict-marked clients
        before running the round's decode column, so the decode rides
        THIS wave's single decode dispatch instead of a follow-on
        round's."""
        bank = self.bank
        if not bank.verdict_touched[self.index]:
            return
        bank.verdict_touched[self.index] = False
        if self.delivered:
            return
        # stage any echo-quorum decode now (same guards as
        # after_crypto_flush; _request_decode dedups staged roots)
        if self._ready_root is None:
            root = bank.echo_quorum_root(self.index)
            if root is not None and root not in self._bad_roots:
                self._request_decode(root)
        # a staged decode may just have reached k shards — stay on
        # the hub's dirty list so this wave round (or the next)
        # collects it (no decode staged -> nothing new to offer)
        if self._decode_req:
            self.hub.mark_dirty(self)

    def _make_decode_cb(self, root: bytes):
        def cb(data) -> None:
            if data is None:
                self._bad_roots.add(root)
                return
            try:
                self._decoded[root] = join_payload(data)
            except ValueError:  # corrupt length framing from proposer
                self._bad_roots.add(root)

        return cb

    def after_crypto_flush(self) -> None:
        """Quorum logic over freshly-verified state; new decode
        requests staged here are picked up by the flush loop's next
        collection round."""
        if self.delivered:
            return
        # N-f verified ECHOs -> stage decode (READY follows a
        # successful root recheck, docs/RBC-EN.md:35-39); one root at
        # most has them (EchoBank.quorum_row)
        if self._ready_root is None:
            root = self.bank.echo_quorum_root(self.index)
            if root is not None and root not in self._bad_roots:
                self._request_decode(root)
                if root in self._decoded:
                    self._send_ready(root)
        for root in self.bank.ready_roots(self.index):
            if self.delivered:
                break
            self._maybe_deliver(root)


__all__ = ["RBC", "MAX_SHARD_BYTES"]
