"""Single framework configuration object.

The reference declares an (empty) ``Config`` struct as the intended
one-stop config (reference cleisthenes.go:3-4, consumed by
``NewRBC(config cleisthenes.Config)`` at rbc/rbc.go:38); its real knobs
live in constructor args (``NewHoneyBadger(batchSize, nodes)``,
honeybadger.go:36) and constants (``DefaultDialTimeout = 3s``,
comm.go:107-109; channel caps 200, conn.go:60-61).  Here the config is a
real dataclass carrying every knob, including the TPU-build additions:
``crypto_backend`` (the ``--crypto=tpu`` flag from BASELINE.json) and
the device-mesh layout for the batched crypto plane.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


# The both-arms registry (staticcheck ARM001): every flag named here
# selects between a fast path and a LIVE byte-equivalence comparison
# arm, and the whole-program analyzer cross-checks the declaration —
# each entry must be a bool Config field, read by the package, pinned
# explicitly (flag=True/False) in the equivalence tests, and a
# perfgate fingerprint key (a mode flip must never gate against the
# other mode's trend records).  Adding an arm seam = add its flag
# here + the fingerprint key + the pinned equivalence test, or the
# analyzer gates the merge.
ARM_FLAGS = (
    "epoch_pipelining",
    "order_then_settle",
    "attested_log",
    "reduced_quorum",
    # int-valued arm: lanes=1 is the byte-equivalence baseline arm,
    # lanes>1 the shard-out fast path (ARM001 accepts int flags whose
    # tests pin >= 2 distinct values; see tools/staticcheck).
    "lanes",
)

DEFAULT_DIAL_TIMEOUT_S = 3.0  # reference comm.go:107-109
# K-deep pipelined frontiers (Config.pipeline_depth): the protocol
# plane may run at most this many epochs' RBC/BBA concurrently.  The
# cap is the demux window's forward horizon
# (protocol.honeybadger.EPOCH_HORIZON, cross-checked there): an
# in-flight epoch past the horizon could not be delivered to a peer
# at the same frontier.
MAX_PIPELINE_DEPTH = 8
# Horizontal shard-out (Config.lanes): at most this many parallel
# consensus lanes over one roster.  The cap bounds the per-node state
# multiplier (S lane instances share one hub/coalescer/WAL) and keeps
# the lane id in a u32 wire field with headroom to spare.
MAX_LANES = 8
DEFAULT_CHANNEL_CAPACITY = 200  # reference conn.go:60-61 (out/read chans)
# Self-healing dial layer (transport/host.py): first retry delay and
# the cap of the exponential backoff.  The reference redials never
# (a lost stream stays lost); a fixed-interval retry is the other
# failure mode — it synchronizes a whole roster's redial storms.
DEFAULT_DIAL_RETRY_BASE_S = 0.05
DEFAULT_DIAL_RETRY_MAX_S = 5.0


@dataclasses.dataclass
class Config:
    """Framework-wide configuration.

    Attributes:
      n: number of validators in the network (N).
      f: Byzantine fault budget; requires N >= 3f+1
         (reference docs/BBA-EN.md:26, docs/HONEYBADGER-EN.md:35).
         Defaults to floor((n-1)/3), the maximum tolerable.
      batch_size: target committed transactions per epoch (B). The
        effective per-node proposal is B/N randomly sampled from the
        head of the queue (reference honeybadger.go:36-49,62-104;
        docs/HONEYBADGER-EN.md:49-56).
      crypto_backend: 'cpu' (numpy reference), 'cpp' (native compiled
        GF kernels) or 'tpu' (batched JAX/XLA kernels) — the
        BatchCrypto/ErasureCoder seam from BASELINE.json.
      dial_timeout_s: client dial timeout (reference comm.go:107-109).
      dial_retry_base_s / dial_retry_max_s: redial policy for the
        self-healing gRPC transport — capped exponential backoff with
        seeded jitter, both for boot-time dials and for streams lost
        mid-run (transport/host.py, transport/health.py).
      channel_capacity: per-connection mailbox depth (conn.go:60-61).
      ledger_fsync: fsync-on-commit policy for the durable batch log
        (core/ledger.py).  False (default) flushes to the OS on every
        append — surviving process crashes; True additionally fsyncs —
        surviving host power loss, at ~ms/commit cost.
      ledger_checkpoint_every: append a dedup-set checkpoint record to
        the batch log every this-many commits, so a restart seeds the
        duplicate filter from the checkpoint instead of re-deriving it
        from every logged batch.  0 disables checkpointing.
      seed: None (default) draws batch-sampling randomness from the OS
        CSPRNG — production mode, keeping proposal selection
        unpredictable (part of HBBFT's censorship-resistance story).
        An int makes sampling deterministic, for tests/benchmarks only.
      coin_seed: shared setup seed for the threshold common-coin and
        TPKE key generation in trusted-dealer mode.
      mesh_shape: optional ('v', 'l') device-mesh layout — (validator
        axis, shard-length axis) — for sharding the crypto plane
        across TPU devices via parallel.mesh.CryptoMesh; None means
        single-device.  Only consumed by the 'tpu' backend.
      trace: enable the per-node flight recorder (utils/trace.py):
        quorum crossings, hub flushes, wave boundaries and WAL
        appends record into a bounded ring, mergeable into one
        Perfetto-loadable artifact by tools/tracetool.py.  False (the
        default) constructs NO recorder at all — instrumentation
        sites hold None and the hot path pays one identity check.
      trace_buffer: per-node trace ring capacity (newest events win;
        overflow counts as drops in Metrics.snapshot()["trace"]).
      obs_port: opt-in live telemetry endpoints (transport/obs_http.py):
        None (default) serves nothing; 0 binds an ephemeral localhost
        port (tests/demo); N binds 127.0.0.1:N.  Serves /metrics
        (Prometheus text exposition), /healthz (UP/DEGRADED/DOWN from
        peer health + SLO watchdogs) and /vars (full JSON snapshot +
        sampled time series) on ValidatorHost and SimulatedCluster.
      obs_sample_period_s: telemetry sampling cadence for the bounded
        time-series rings (utils/timeseries.py) when the obs plane is
        on; each tick also runs the SLO watchdog checks.
      slo_stall_factor / slo_stall_grace_s: the epoch-stall watchdog's
        commit budget is max(grace, factor * recent epoch p50) — no
        commit within it while txs are pending flips health to DOWN
        (utils/watchdog.py).
      slo_queue_depth: pending-transaction depth above which the
        backpressure alarm fires (ingress outrunning commit).
      slo_peer_lag_epochs: epoch-frontier gap above which a trailing
        peer counts as lagging (peer-lag detector; in-proc clusters).
      order_then_settle: two-frontier commit split (see the field
        comment below): ciphertext-ordered commit at ACS output, with
        threshold decryption trailing in an idle-driven settler.
      pipeline_depth: K-deep pipelined frontiers (see the field
        comment below): epochs [ordered frontier, ordered frontier +
        K - 1] run their RBC propose/ECHO/READY and BBA rounds
        concurrently; ordering still advances strictly in epoch
        order and parks at decrypt_lag_max.  1 (lockstep — only the
        frontier epoch runs, today's pre-K behavior byte-identically)
        .. MAX_PIPELINE_DEPTH (the demux window's forward horizon).
        Effective only on the pipelined two-frontier path
        (epoch_pipelining and order_then_settle both on — the
        epoch_pipelining arm flag gates the whole K-deep plane).
      decrypt_lag_max: backpressure bound on ordered-ahead epochs
        (ordered frontier - settled frontier); also the settle-stall
        SLO watchdog's lag budget.
      reconfig_lead: dynamic membership (protocol.reconfig): epochs
        between the settlement completing a reshare ceremony and the
        new roster's activation; must exceed pipeline_depth +
        decrypt_lag_max so the activation boundary lands past every
        epoch the old roster could already have ordered OR still
        have in flight in the K-deep window.

    The wave seams have ONE path each and no option: on a transport
    that promises an idle callback the CryptoHub flushes once per
    message wave; transports decode a wave's frames through the
    shared-prefix memo, verify its MACs in one
    Authenticator.verify_wire_many call and hand it to the handler in
    one serve_wave call (protocol.router.WaveRouter: one batch handler
    dispatch per (message kind, wave)); a coalescer flush signs in one
    Authenticator.sign_wire_wave pass and a wave's coin-share issues
    pool in the hub's coin column.
    """

    n: int = 4
    f: Optional[int] = None
    batch_size: int = 256
    crypto_backend: str = "cpu"
    dial_timeout_s: float = DEFAULT_DIAL_TIMEOUT_S
    dial_retry_base_s: float = DEFAULT_DIAL_RETRY_BASE_S
    dial_retry_max_s: float = DEFAULT_DIAL_RETRY_MAX_S
    channel_capacity: int = DEFAULT_CHANNEL_CAPACITY
    ledger_fsync: bool = False
    ledger_checkpoint_every: int = 32
    seed: Optional[int] = None
    coin_seed: int = 1
    mesh_shape: Optional[tuple] = None
    trace: bool = False
    trace_buffer: int = 1 << 16
    obs_port: Optional[int] = None
    obs_sample_period_s: float = 1.0
    slo_stall_factor: float = 8.0
    slo_stall_grace_s: float = 10.0
    slo_queue_depth: int = 100_000
    slo_peer_lag_epochs: int = 8
    # Epoch pipelining (BASELINE config 5): propose into epoch e+1 the
    # moment epoch e's ACS outputs, so e+1's RS-encode/Merkle-forest
    # and VAL/ECHO exchange overlap e's decryption-share phase.
    # Commit order is unaffected (commits gate on the epoch counter).
    epoch_pipelining: bool = True
    # Order-then-decrypt (the two-frontier commit split, after "The
    # Latency Price of Threshold Cryptosystems in Blockchains"): at
    # ACS output the epoch commits its CIPHERTEXT-ORDERED batch — a
    # deterministic {proposer: ct} record, WAL-durable as a COrd
    # record — and the epoch counter advances immediately, so epoch
    # e+1's RBC/BBA runs at full speed while epoch e's TPKE dec-share
    # verify/combine trails in a settler driven from the transports'
    # idle callbacks.  The settled frontier writes the plaintext CLOG
    # record, applies the dedup filter and fires on_commit, strictly
    # in epoch order.  False = the coupled arm: commit blocks on the
    # full decryption exchange exactly as before (kept as the
    # byte-equivalence comparison arm — same seed, same settled
    # plaintext log).
    order_then_settle: bool = True
    # K-deep pipelined epoch frontiers (ISSUE 15, the PR-8 split
    # generalized): epochs [self.epoch, self.epoch + K - 1] run their
    # RBC/BBA concurrently against the K-deep ordered window, each
    # with its own _EpochState — K concurrent epochs' traffic lands
    # in the SAME delivery waves, so the hub/router/egress columnar
    # planes amortize K epochs' crypto into one dispatch per kind per
    # wave.  Ordering still advances strictly in epoch order
    # (_maybe_order) and parks at decrypt_lag_max exactly as at depth
    # 1.  Depth 1 reproduces the pre-K behavior byte-identically and
    # stays live as the comparison arm (tests/test_pipeline_depth.py);
    # the plane as a whole is gated by the epoch_pipelining ARM flag
    # (epoch_pipelining=False forces lockstep regardless of depth).
    pipeline_depth: int = 2
    # Bounded ordered-but-unsettled window: the ordered frontier may
    # run at most this many epochs ahead of the settled frontier
    # before ordering parks (backpressure).  A Byzantine coalition
    # delaying settlement (share forgery) therefore stalls ordering
    # AT this bound, never unboundedly ahead of durable plaintext.
    decrypt_lag_max: int = 4
    # Dynamic membership (protocol.reconfig): epochs between the
    # SETTLEMENT that completes a reshare ceremony's qualified dealer
    # set and the new roster's activation epoch.  Must exceed
    # decrypt_lag_max: when the completing epoch settles, the ordered
    # frontier is at most decrypt_lag_max ahead, so no epoch at or
    # past the activation boundary can have been ordered under the
    # OLD roster — the switch point is clean on every honest node.
    reconfig_lead: int = 8
    # --- ingress plane (transport/ingress.py + core/mempool.py) ---
    # mempool_capacity > 0 mounts the fee-priority mempool ahead of
    # the FIFO TxQueue: client submissions admit through it (dedup,
    # per-client + global backpressure, priority eviction) and batch
    # selection drains it highest-fee-first into the TxQueue seam.
    # 0 disables the mempool: add_transaction feeds the TxQueue
    # directly, exactly the pre-ingress behavior.
    mempool_capacity: int = 0
    # per-client pending cap: a client with this many unsettled
    # admitted txs gets RETRY_AFTER (open-loop fairness: one hot
    # client cannot monopolize the global capacity).
    mempool_client_cap: int = 64
    # bounded ingress-side seen-set (digest ring): resubmits of
    # pending or recently-settled txs ack DUPLICATE without re-entry.
    # Coordinated with (not replacing) the settle-time dedup filter:
    # this ring is the fast front-door check, the committed-history
    # filter at batch selection remains the authoritative one.
    mempool_seen_cap: int = 1 << 16
    # the RETRY_AFTER hint handed to backpressured clients, in ms.
    mempool_retry_after_ms: int = 100
    # TCP port for the client-facing gRPC ingress service (None =
    # no listener; the in-process twin is always available).
    ingress_port: Optional[int] = None
    # --- attested trust model (protocol/attest.py) ----------------
    # attested_log mounts the simulated-TEE attestation plane: every
    # outbound frame carries a MAC'd (incarnation, counter) attestation
    # issued by a per-node AttestationVault that REFUSES to attest two
    # different digests for the same protocol slot — so an equivocating
    # sender is forced to ship counter-fork evidence (a refused=1
    # trailer); honest receivers record the accusation and reject the
    # lied frames themselves, so equivocation degrades to omission of
    # exactly the forked statements while the sender's honest traffic
    # keeps feeding the quorums (load-bearing at n = 2f+1).  The
    # vault sits BELOW the protocol plane's Behavior seam
    # (protocol.byzantine): a semantic adversary can rewrite payloads
    # but cannot forge, fork or suppress attestations.  False is the
    # baseline arm: no trailers, no per-link counter state, frames
    # byte-identical to the pre-attestation wire format.
    attested_log: bool = False
    # reduced_quorum switches the large-quorum arithmetic (the 2f+1
    # READY/deliver/bin_values/TERM-halt thresholds) to n-f, the
    # TEE-reduced form of arxiv 2102.01970: with equivocation excluded
    # by the attested log, any two (n-f)-quorums of an n >= 2f+1
    # roster intersect in a non-equivocating node and safety holds at
    # rosters a third smaller.  f defaults to floor((n-1)/2) in this
    # mode and Config enforces n >= 2f+1 instead of 3f+1.  At the
    # baseline roster shape n = 3f+1 exactly, n-f == 2f+1, so the
    # False arm's arithmetic is bit-identical to the historical
    # thresholds.  Sound only together with attested_log (enforced).
    reduced_quorum: bool = False
    # --- horizontal shard-out (ISSUE 20) --------------------------
    # lanes = S runs S independent HBBFT lane instances over the SAME
    # validator set, transports and roster schedule.  Admission
    # tx-hash-partitions across lanes (core.merge.lane_of: seeded
    # sha256(seed || digest) % S, node- and PYTHONHASHSEED-identical);
    # each lane keeps its own epoch frontiers and lane-tagged WAL
    # record stream, and the settled frontiers merge into ONE
    # deterministic total order (core.merge.MergeCursor: epoch-major,
    # lane-minor — a pure function of the committed bytes, so honest
    # nodes' merged orders are byte-identical).  Lane traffic rides
    # the SAME coalescer flushes, delivery waves and hub columns as
    # lane 0 (LanePayload wire framing + lane-qualified hub scopes),
    # so S lanes' crypto amortizes into the same native dispatches
    # instead of multiplying them.  1 (default) is byte-identical to
    # the pre-lane build: no LanePayload ever hits the wire, no lane
    # records hit the WAL.  Dynamic membership (RECONFIG) is not
    # supported at lanes > 1.
    lanes: int = 1

    def __post_init__(self) -> None:
        if self.n < 1:
            raise ValueError(f"n={self.n} must be >= 1")
        if self.reduced_quorum and not self.attested_log:
            raise ValueError(
                "reduced_quorum=True requires attested_log=True: the "
                "n-f quorum intersection argument only holds once "
                "equivocation is excluded by the attested sender log"
            )
        if self.f is None:
            self.f = (
                (self.n - 1) // 2
                if self.reduced_quorum
                else (self.n - 1) // 3
            )
        if self.f < 0:
            raise ValueError(f"f={self.f} must be >= 0")
        if self.reduced_quorum:
            if self.n < 2 * self.f + 1:
                raise ValueError(
                    f"n={self.n} must be >= 2f+1={2 * self.f + 1} "
                    "in reduced-quorum mode (arxiv 2102.01970)"
                )
        elif self.n < 3 * self.f + 1:
            raise ValueError(
                f"n={self.n} must be >= 3f+1={3 * self.f + 1} "
                "(docs/BBA-EN.md:26: t < n/3)"
            )
        if self.dial_retry_base_s <= 0 or (
            self.dial_retry_max_s < self.dial_retry_base_s
        ):
            raise ValueError(
                f"dial retry policy base={self.dial_retry_base_s} "
                f"max={self.dial_retry_max_s}: need 0 < base <= max"
            )
        if self.ledger_checkpoint_every < 0:
            raise ValueError(
                f"ledger_checkpoint_every={self.ledger_checkpoint_every} "
                "must be >= 0 (0 disables checkpoints)"
            )
        if self.crypto_backend not in ("cpu", "cpp", "tpu"):
            raise ValueError(f"unknown crypto_backend {self.crypto_backend!r}")
        if self.trace_buffer <= 0:
            raise ValueError(
                f"trace_buffer={self.trace_buffer} must be > 0"
            )
        if self.obs_port is not None and not (0 <= self.obs_port <= 65535):
            raise ValueError(
                f"obs_port={self.obs_port} must be None or 0..65535"
            )
        if self.obs_sample_period_s <= 0:
            raise ValueError(
                f"obs_sample_period_s={self.obs_sample_period_s} "
                "must be > 0"
            )
        if self.slo_stall_factor <= 0 or self.slo_stall_grace_s <= 0:
            raise ValueError(
                f"stall SLO needs factor>0 grace>0, got "
                f"{self.slo_stall_factor}/{self.slo_stall_grace_s}"
            )
        if self.slo_queue_depth <= 0 or self.slo_peer_lag_epochs <= 0:
            raise ValueError(
                f"SLO thresholds must be > 0: queue_depth="
                f"{self.slo_queue_depth} peer_lag="
                f"{self.slo_peer_lag_epochs}"
            )
        if self.decrypt_lag_max < 1:
            raise ValueError(
                f"decrypt_lag_max={self.decrypt_lag_max} must be >= 1 "
                "(1 = order at most one epoch ahead of settlement)"
            )
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth={self.pipeline_depth} must be >= 1 "
                "(1 = lockstep: only the ordered frontier's epoch "
                "runs its RBC/BBA)"
            )
        if self.pipeline_depth > MAX_PIPELINE_DEPTH:
            raise ValueError(
                f"pipeline_depth={self.pipeline_depth} exceeds "
                f"MAX_PIPELINE_DEPTH={MAX_PIPELINE_DEPTH} (the demux "
                "window's forward horizon: an in-flight epoch past it "
                "could not reach a same-frontier peer)"
            )
        if self.reconfig_lead <= self.pipeline_depth + self.decrypt_lag_max:
            raise ValueError(
                f"reconfig_lead={self.reconfig_lead} must exceed "
                f"pipeline_depth + decrypt_lag_max = "
                f"{self.pipeline_depth + self.decrypt_lag_max} (the "
                "roster switch point must land past every epoch the "
                "old roster could already have ordered or still have "
                "in flight in the K-deep window)"
            )
        if self.mempool_capacity < 0:
            raise ValueError(
                f"mempool_capacity={self.mempool_capacity} must be "
                ">= 0 (0 disables the mempool)"
            )
        if self.mempool_client_cap < 1:
            raise ValueError(
                f"mempool_client_cap={self.mempool_client_cap} must "
                "be >= 1"
            )
        if self.mempool_seen_cap < 1:
            raise ValueError(
                f"mempool_seen_cap={self.mempool_seen_cap} must be >= 1"
            )
        if self.mempool_retry_after_ms < 0:
            raise ValueError(
                f"mempool_retry_after_ms={self.mempool_retry_after_ms} "
                "must be >= 0"
            )
        if self.ingress_port is not None and not (
            0 <= self.ingress_port <= 65535
        ):
            raise ValueError(
                f"ingress_port={self.ingress_port} must be None or "
                "0..65535"
            )
        if not (1 <= self.lanes <= MAX_LANES):
            raise ValueError(
                f"lanes={self.lanes} must be 1..{MAX_LANES} (S parallel "
                "consensus lanes over one roster; 1 = single-lane "
                "pre-shard-out behavior)"
            )
        if self.mesh_shape is not None:
            from cleisthenes_tpu.parallel.mesh import validate_mesh_shape

            self.mesh_shape = validate_mesh_shape(self.mesh_shape)

    @property
    def data_shards(self) -> int:
        """K = N - 2f data shards for RS coding (docs/RBC-EN.md:30)."""
        return self.n - 2 * self.f

    @property
    def parity_shards(self) -> int:
        """2f parity shards so any N-2f of N shards reconstruct."""
        return 2 * self.f

    @property
    def decryption_threshold(self) -> int:
        """f+1 decryption shares recover a TPKE plaintext
        (docs/HONEYBADGER-EN.md:40-42, docs/THRESHOLD_ENCRYPTION-EN.md:33-36)."""
        return self.f + 1

    @property
    def quorum_large(self) -> int:
        """The large-quorum threshold: READY amplification to deliver,
        BVAL bin_values growth, TERM halt.  Baseline 2f+1; in
        reduced-quorum mode n-f (identical when n = 3f+1 exactly, so
        every historical roster's arithmetic is unchanged).  The f+1
        relay thresholds and the n-f input-wait thresholds are mode-
        independent."""
        return (self.n - self.f) if self.reduced_quorum else (2 * self.f + 1)
