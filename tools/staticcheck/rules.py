"""The rule catalog: this codebase's real hazard classes.

Every rule documents WHAT it flags, WHERE (scope flags), and WHY the
hazard can fork a replay or a ledger.  Adding a rule = subclass with
``id``/``doc``/``check(ctx)`` + the ``@rule`` decorator + a fixture
pair under tests/staticcheck_fixtures/ (see docs/ARCHITECTURE.md).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.staticcheck.core import FileContext, Finding, rule

# ---------------------------------------------------------------------------
# DET001: wall clocks & unseeded randomness in the determinism plane
# ---------------------------------------------------------------------------

# Calls whose RESULT depends on when/where the process runs.  Any of
# these reachable from protocol/core/ops state can diverge two replays
# of the same seeded schedule.
_DET001_EXACT = frozenset(
    (
        "time.time",
        "time.time_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "os.urandom",
        "uuid.uuid4",
        "random.SystemRandom",
        "random.random",
        "random.randint",
        "random.randrange",
        "random.randbytes",
        "random.getrandbits",
        "random.choice",
        "random.choices",
        "random.shuffle",
        "random.sample",
        "random.uniform",
    )
)
# every attribute of these modules is OS entropy by definition
_DET001_MODULES = frozenset(("secrets",))


@rule
class Det001WallClockAndEntropy:
    id = "DET001"
    doc = (
        "no wall clock (time.time/monotonic/perf_counter) or unseeded "
        "randomness (random module fns, SystemRandom, secrets, "
        "os.urandom) in the determinism plane (protocol/, core/, ops/)"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not ctx.in_plane:
            return
        call_of: Dict[int, ast.Call] = {}
        for n in ast.walk(ctx.tree):
            if isinstance(n, ast.Call):
                call_of[id(n.func)] = n
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.Attribute, ast.Name)):
                continue
            # only flag loads (uses), not the import statements
            if not isinstance(getattr(node, "ctx", None), ast.Load):
                continue
            dotted = ctx.resolve(node)
            if dotted is None:
                continue
            mod = dotted.split(".")[0]
            if dotted in _DET001_EXACT or mod in _DET001_MODULES:
                # a bare module Name ("time") is not itself a use; the
                # full dotted Attribute node is what gets reported
                if isinstance(node, ast.Name) and dotted == mod:
                    continue
                yield ctx.finding(
                    self.id,
                    node,
                    f"{dotted} is nondeterministic; the determinism "
                    "plane must derive all state from seeded inputs "
                    "(route sanctioned entropy through "
                    "utils.determinism or pragma with justification)",
                )
            elif dotted == "random.Random":
                # seeded Random(x) is fine; zero-arg Random() seeds
                # from the OS
                call = call_of.get(id(node))
                if call is not None and not (call.args or call.keywords):
                    yield ctx.finding(
                        self.id,
                        node,
                        "random.Random() without a seed draws OS "
                        "entropy; pass an explicit seed",
                    )


# ---------------------------------------------------------------------------
# DET002: hash-order iteration over sets in the determinism plane
# ---------------------------------------------------------------------------
#
# CPython set/frozenset iteration order for str/bytes elements depends
# on PYTHONHASHSEED; two honest nodes iterating "the same" set can walk
# it in different orders and serialize different bytes.  (dicts are
# insertion-ordered since 3.7, so dict iteration is deterministic
# whenever insertions are — sets are the hazard.)  The rule flags
# iteration sinks (for/comprehension iterables, list()/tuple()/
# max()/min() args) whose expression is statically known to be a set:
# a set()/frozenset() call, a set literal/comprehension, or a local /
# self attribute assigned or annotated as one.  Wrap the boundary in
# sorted() — or restructure to an insertion-ordered dict — to fix.

_SET_ANNOTATIONS = frozenset(("set", "frozenset", "Set", "FrozenSet"))
_ORDER_SINK_CALLS = frozenset(("list", "tuple", "max", "min"))


def _is_set_expr(
    node: ast.AST, local_sets: Set[str], attr_sets: Set[str]
) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        if node.func.id in ("set", "frozenset"):
            return True
    if isinstance(node, ast.Name):
        return node.id in local_sets
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr in attr_sets
    return False


def _annotation_is_set(ann: ast.AST) -> bool:
    # matches set / Set[...] / typing.Set[...] / frozenset
    if isinstance(ann, ast.Subscript):
        ann = ann.value
    if isinstance(ann, ast.Attribute):
        return ann.attr in _SET_ANNOTATIONS
    return isinstance(ann, ast.Name) and ann.id in _SET_ANNOTATIONS


def _collect_set_names(
    root: ast.AST,
) -> Tuple[Set[str], Set[str]]:
    """(local names, self attributes) assigned/annotated as sets
    anywhere in ``root`` — one flat namespace per file is precise
    enough for this tree's naming discipline."""
    local_sets: Set[str] = set()
    attr_sets: Set[str] = set()

    def note_target(target: ast.AST, is_set: bool) -> None:
        if isinstance(target, ast.Name):
            (local_sets.add if is_set else local_sets.discard)(target.id)
        elif (
            isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Name)
            and target.value.id == "self"
        ):
            (attr_sets.add if is_set else attr_sets.discard)(target.attr)

    for node in ast.walk(root):
        if isinstance(node, ast.Assign):
            is_set = _is_set_expr(node.value, local_sets, attr_sets)
            for t in node.targets:
                note_target(t, is_set)
        elif isinstance(node, ast.AnnAssign):
            note_target(node.target, _annotation_is_set(node.annotation))
    return local_sets, attr_sets


@rule
class Det002SetIterationOrder:
    id = "DET002"
    doc = (
        "no iteration over unordered set/frozenset in the determinism "
        "plane where order can reach wire or ledger bytes; wrap the "
        "boundary in sorted()"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not ctx.in_plane:
            return
        local_sets, attr_sets = _collect_set_names(ctx.tree)

        def flag(expr: ast.AST, what: str) -> Optional[Finding]:
            if _is_set_expr(expr, local_sets, attr_sets):
                return ctx.finding(
                    self.id,
                    expr,
                    f"{what} iterates a set in hash order "
                    "(PYTHONHASHSEED-dependent); wrap in sorted() or "
                    "use an insertion-ordered dict",
                )
            return None

        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.For, ast.AsyncFor)):
                f = flag(node.iter, "for loop")
                if f:
                    yield f
            elif isinstance(
                node,
                (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp),
            ):
                for gen in node.generators:
                    f = flag(gen.iter, "comprehension")
                    if f:
                        yield f
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in _ORDER_SINK_CALLS
                and len(node.args) == 1
            ):
                f = flag(node.args[0], f"{node.func.id}()")
                if f:
                    yield f


# ---------------------------------------------------------------------------
# DET003: crypto verify/decode must route through the hub's columnar seam
# ---------------------------------------------------------------------------
#
# The wave-columnar refactor (ISSUE 7) moved every protocol-plane
# batch-crypto execution behind CryptoHub: clients stage work and
# drain it into a HubWave's typed columns; ONE dispatch per work kind
# runs per flush.  A direct BatchCrypto verify/decode call from
# protocol/ code outside hub.py silently erodes that seam back to
# scalar per-instance dispatch — the exact regression the refactor
# removed (hub_dispatches_cluster 24-37/epoch -> O(work kinds)).
# The rule flags calls to the verify/decode surfaces of the crypto
# layer (merkle verify_branch/verify_batch, RS decode_batch/
# decode_recheck_batch, threshold-share verify_* — as methods or as
# from-imported ops functions) anywhere under protocol/ except
# hub.py itself.  Legitimate inline checks (RBC's single VAL-branch
# precheck; the lockstep spmd.py plane, which IS its own columnar
# batch layer and never touches the hub) carry allow[DET003] pragmas
# with justifications.

_DET003_CALLS = frozenset(
    (
        "verify_branch",
        "verify_batch",
        "decode_batch",
        "decode_recheck_batch",
        "verify_shares",
        "verify_share_groups",
        "verify_and_combine_share_groups",
        "verify_dec_shares",
    )
)
_DET003_EXEMPT_FILES = frozenset(("hub.py",))


@rule
class Det003HubColumnarSeam:
    id = "DET003"
    doc = (
        "no direct BatchCrypto verify/decode calls from protocol/ "
        "outside hub.py; stage the work and drain it through the "
        "CryptoHub wave (drain_pending) so it batches columnar"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        parts = ctx.relpath.split("/")
        if "protocol" not in parts or parts[-1] in _DET003_EXEMPT_FILES:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = None
            if isinstance(func, ast.Attribute):
                if func.attr in _DET003_CALLS:
                    name = func.attr
            elif isinstance(func, ast.Name):
                # from-imported ops function (ctx.resolve maps the
                # local name through import aliases)
                dotted = ctx.resolve(func)
                if (
                    dotted
                    and ".ops." in f".{dotted}"
                    and dotted.rsplit(".", 1)[-1] in _DET003_CALLS
                ):
                    name = dotted
            if name is not None:
                yield ctx.finding(
                    self.id,
                    node,
                    f"direct crypto dispatch {name}() bypasses the "
                    "hub's columnar seam; stage the work and offer it "
                    "via drain_pending(wave) instead",
                )


# ---------------------------------------------------------------------------
# DET004: protocol ingest must cross the wave-router seam per WAVE
# ---------------------------------------------------------------------------
#
# The wave-routed ingest refactor (ISSUE 10) moved the inbound handler
# boundary to wave granularity: transports hand a delivery wave's
# verified frames to the handler in ONE serve_wave call, and the
# WaveRouter makes one batch dispatch per message kind — replacing the
# per-payload HoneyBadger.handle_message -> ACS -> RBC/BBA chain that
# owned the transport stage share after PR 9.  A per-frame
# ``handler.serve_request(...)`` / ``x.handle_message(...)`` call from
# transport/ code silently erodes that seam back to one Python call
# chain per payload — the exact regression the router removed.  The
# sanctioned sites (local self-delivery short-circuits and the
# non-wave-handler fallbacks) carry allow[DET004] pragmas with
# justifications.

_DET004_CALLS = frozenset(("serve_request", "handle_message"))


@rule
class Det004WaveIngestSeam:
    id = "DET004"
    doc = (
        "no per-frame handler dispatch (serve_request/handle_message) "
        "from transport/ outside the wave-router seam; buffer the "
        "wave and hand it over in one serve_wave call"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        parts = ctx.relpath.split("/")
        if "transport" not in parts:
            return
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _DET004_CALLS
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"per-frame {node.func.attr}() dispatch bypasses "
                    "the wave-router seam; buffer the wave and hand "
                    "it to the handler in one serve_wave call",
                )


# ---------------------------------------------------------------------------
# DET005: epoch-scoped code must resolve the roster through the
# roster-version accessor
# ---------------------------------------------------------------------------
#
# Dynamic membership (ISSUE 12) made the roster a VERSIONED value:
# every epoch resolves n/f/keys/membership through
# ``roster_for(epoch)`` / the epoch state's ``view``.  A direct read
# of the construction-time constants (``self.config.n``,
# ``self.config.f``, ``self.members``, ``self._member_set``,
# ``self.keys``) from code that handles a PARTICULAR epoch silently
# re-pins the roster to whatever was active at construction — correct
# right up until the first RECONFIG crosses, then a fork/liveness
# bug that only a roster-change schedule can catch.  The rule flags
# those reads inside any function that takes an epoch parameter, in
# the protocol files whose objects OUTLIVE epochs; per-epoch
# instances (ACS/RBC/BBA and their banks — constructed WITH a
# version's config) are exempt, as is the reshare plane itself.

_DET005_EXEMPT_FILES = frozenset(
    (
        "acs.py",  # per-epoch: constructed with the epoch's view
        "rbc.py",
        "bba.py",
        "echobank.py",
        "votebank.py",
        "hub.py",  # roster-agnostic batch executor (geometry rides
        # with each request)
        "spmd.py",  # lockstep executor: fixed-roster by definition
        "byzantine.py",  # adversary plane: lies are the point
        "reconfig.py",  # the accessor's own implementation layer
    )
)
_DET005_CONFIG_FIELDS = frozenset(("n", "f", "decryption_threshold"))
_DET005_SELF_ATTRS = frozenset(("members", "_member_set", "keys"))
# Lane shard-out (ISSUE 20) made the epoch frontier a PER-LANE value:
# code handed a lane index must resolve frontiers through the
# lane-indexed accessor (self.lanes[lane].epoch / the merged_*
# accessors), never the bare primary-lane attributes — a bare read is
# correct at lanes=1 and silently pins lane 0's frontier the moment a
# second lane exists.
_DET005_LANE_FRONTIER_ATTRS = frozenset(
    ("epoch", "settled_epoch", "committed_batches")
)


@rule
class Det005RosterVersionAccessor:
    id = "DET005"
    doc = (
        "epoch-scoped protocol code (functions taking an epoch "
        "parameter) must resolve n/f/keys/membership via "
        "roster_for(epoch) / the epoch state's view, not the "
        "construction-time self.config.n / self.members / self.keys; "
        "lane-scoped code (functions taking a lane parameter) must "
        "resolve frontiers via the lane-indexed accessor "
        "(self.lanes[lane] / merged_*), not the bare primary-lane "
        "self.epoch / self.settled_epoch / self.committed_batches"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        parts = ctx.relpath.split("/")
        if "protocol" not in parts or parts[-1] in _DET005_EXEMPT_FILES:
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            args = fn.args
            names = [
                a.arg
                for a in (
                    args.posonlyargs + args.args + args.kwonlyargs
                )
            ]
            if any("lane" in a for a in names):
                yield from self._check_lane_scoped(ctx, fn)
            if not any("epoch" in a for a in names):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Attribute):
                    continue
                inner = node.value
                # self.config.n / self.config.f / ...
                if (
                    node.attr in _DET005_CONFIG_FIELDS
                    and isinstance(inner, ast.Attribute)
                    and inner.attr == "config"
                    and _self_attr(inner) == "config"
                ):
                    yield ctx.finding(
                        self.id,
                        node,
                        f"epoch-scoped {fn.name}() reads "
                        f"self.config.{node.attr}; resolve the "
                        "epoch's roster via roster_for(epoch)/"
                        "es.view instead",
                    )
                # self.members / self._member_set / self.keys
                elif _self_attr(node) in _DET005_SELF_ATTRS:
                    yield ctx.finding(
                        self.id,
                        node,
                        f"epoch-scoped {fn.name}() reads "
                        f"self.{node.attr} (the ACTIVE roster); "
                        "resolve the epoch's roster via "
                        "roster_for(epoch)/es.view instead",
                    )

    def _check_lane_scoped(
        self, ctx: FileContext, fn: ast.AST
    ) -> Iterator[Finding]:
        """Lane-scoped code reading the bare primary-lane frontier
        (Load contexts only: lane objects still initialize their own
        ``self.epoch``).  Constructors are exempt: an object built
        WITH a lane id IS that lane, and its __init__ legitimately
        wires/replays its own frontier — the hazard is cross-lane
        aggregation code handed a lane INDEX."""
        if getattr(fn, "name", "") == "__init__":
            return
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.ctx, ast.Load)
                and node.attr in _DET005_LANE_FRONTIER_ATTRS
                and _self_attr(node) is not None
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"lane-scoped {fn.name}() reads "
                    f"self.{node.attr} (the PRIMARY lane's "
                    "frontier); resolve through the lane-indexed "
                    "accessor self.lanes[lane] / the merged_* "
                    "frontier accessors instead",
                )


# ---------------------------------------------------------------------------
# DET006: egress must cross the wave signer per WAVE
# ---------------------------------------------------------------------------
#
# The egress columnarization (ISSUE 13) moved the outbound signer
# boundary to wave granularity: a coalescer flush hands its whole wave
# of folded bundles to ONE ``Authenticator.sign_wire_wave`` call,
# which encodes each distinct payload body once (shared-prefix
# FrameEncodeMemo) and runs the wave's HMACs as one batched pass.  A
# per-frame ``sign_wire_many(...)`` / ``encode_message(...)`` call
# from protocol/ code or a transport send path silently erodes that
# seam back to one envelope encode + sign pass per post — the exact
# redundancy the wave signer removed.  The sanctioned sites (pre-pool
# boot traffic, non-endpoint test rigs) carry allow[DET006] pragmas
# with justifications; transport/message.py is
# the codec itself and transport/base.py is the authenticator layer
# whose job IS the per-frame encode+sign primitives (the hub.py of
# this seam), so both are exempt.

_DET006_CALLS = frozenset(
    ("sign_wire_many", "encode_message", "sign_wire")
)
_DET006_EXEMPT_FILES = frozenset(("message.py", "base.py"))


@rule
class Det006EgressWaveSeam:
    id = "DET006"
    doc = (
        "no per-frame envelope encode+sign (sign_wire_many/"
        "encode_message) from protocol/ or transport send paths "
        "outside the wave signer; buffer the egress wave and sign it "
        "in one sign_wire_wave call"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        parts = ctx.relpath.split("/")
        if (
            "transport" not in parts and "protocol" not in parts
        ) or parts[-1] in _DET006_EXEMPT_FILES:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = None
            if isinstance(func, ast.Attribute):
                if func.attr in _DET006_CALLS:
                    name = func.attr
            elif isinstance(func, ast.Name):
                # from-imported codec function (ctx.resolve maps the
                # local name through import aliases)
                dotted = ctx.resolve(func)
                if (
                    dotted
                    and dotted.rsplit(".", 1)[-1] in _DET006_CALLS
                ):
                    name = func.id
            if name is not None:
                yield ctx.finding(
                    self.id,
                    node,
                    f"per-frame {name}() encode+sign bypasses the "
                    "wave signer seam; buffer the egress wave and "
                    "sign it in one sign_wire_wave call",
                )


# ---------------------------------------------------------------------------
# CONC001: lock discipline for @guarded_by-annotated attributes
# ---------------------------------------------------------------------------
#
# utils.determinism.guarded_by("_lock", "_attr", ...) declares which
# instance attributes a class's lock protects.  The rule statically
# requires every self._attr access OUTSIDE __init__ to sit lexically
# inside ``with self._lock:``.  Methods named ``*_locked`` are exempt
# by convention: their docstring contract is "caller holds the lock"
# (the annotation documents the boundary; the analyzer enforces it).

_CONC001_EXEMPT = frozenset(("__init__", "__del__"))


def _guarded_decls(cls: ast.ClassDef) -> Dict[str, str]:
    """attr -> lock from guarded_by decorators (string literals only:
    the declaration is meant to be statically readable)."""
    out: Dict[str, str] = {}
    for dec in cls.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        fn = dec.func
        name = fn.attr if isinstance(fn, ast.Attribute) else getattr(
            fn, "id", None
        )
        if name != "guarded_by":
            continue
        strs = [
            a.value
            for a in dec.args
            if isinstance(a, ast.Constant) and isinstance(a.value, str)
        ]
        if len(strs) >= 2:
            lock, attrs = strs[0], strs[1:]
            for a in attrs:
                out[a] = lock
    return out


def _self_attr(node: ast.AST) -> Optional[str]:
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


@rule
class Conc001LockDiscipline:
    id = "CONC001"
    doc = (
        "attributes declared via @guarded_by('_lock', ...) may only be "
        "touched inside a matching `with self._lock:` block "
        "(methods named *_locked are caller-holds-lock by contract)"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            guarded = _guarded_decls(cls)
            if not guarded:
                continue
            for meth in cls.body:
                if not isinstance(
                    meth, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                if meth.name in _CONC001_EXEMPT or meth.name.endswith(
                    "_locked"
                ):
                    continue
                yield from self._check_method(ctx, cls, meth, guarded)

    def _check_method(
        self,
        ctx: FileContext,
        cls: ast.ClassDef,
        meth: ast.AST,
        guarded: Dict[str, str],
    ) -> Iterator[Finding]:
        held: List[str] = []
        findings: List[Finding] = []

        def visit(node: ast.AST) -> None:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                acquired = []
                for item in node.items:
                    attr = _self_attr(item.context_expr)
                    if attr is not None:
                        acquired.append(attr)
                        held.append(attr)
                # the context expressions themselves are lock reads
                for child in node.body:
                    visit(child)
                for _ in acquired:
                    held.pop()
                return
            attr = _self_attr(node)
            if attr is not None and attr in guarded:
                lock = guarded[attr]
                if lock not in held:
                    findings.append(
                        ctx.finding(
                            self.id,
                            node,
                            f"{cls.name}.{meth.name} touches "
                            f"self.{attr} outside `with self.{lock}:` "
                            f"(declared guarded_by {lock!r})",
                        )
                    )
                return  # don't descend: self.X.y is one access
            for child in ast.iter_child_nodes(node):
                visit(child)

        for stmt in meth.body:
            visit(stmt)
        yield from findings


# ---------------------------------------------------------------------------
# CONC002: no blocking calls inside transport handler callbacks
# ---------------------------------------------------------------------------
#
# Handler callbacks (serve_request / handle_* / on_*) run on a
# transport's dispatch thread or inside the deterministic scheduler's
# turn; a time.sleep or raw socket wait there stalls every instance
# behind it (and, in the seeded scheduler, silently changes which
# interleavings are reachable).

_BLOCKING_METHOD_NAMES = frozenset(
    ("accept", "recv", "recvfrom", "recv_into", "sendall")
)
_HANDLER_PREFIXES = ("handle", "_handle", "on_", "_on_", "serve_")


def _is_handler_name(name: str) -> bool:
    return name == "serve_request" or name.startswith(_HANDLER_PREFIXES)


@rule
class Conc002BlockingInHandlers:
    id = "CONC002"
    doc = (
        "no blocking calls (time.sleep, socket accept/recv/sendall, "
        "select) inside transport/protocol handler callbacks"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not (ctx.in_transport or ctx.in_plane):
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _is_handler_name(fn.name):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                dotted = ctx.resolve(node.func)
                if dotted in ("time.sleep", "select.select") or (
                    dotted is not None
                    and dotted.startswith("socket.")
                ):
                    yield ctx.finding(
                        self.id,
                        node,
                        f"handler {fn.name} calls blocking {dotted}",
                    )
                elif (
                    dotted is None
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _BLOCKING_METHOD_NAMES
                ):
                    yield ctx.finding(
                        self.id,
                        node,
                        f"handler {fn.name} calls blocking "
                        f".{node.func.attr}()",
                    )


# ---------------------------------------------------------------------------
# ERR001: swallowed exceptions in protocol/transport code
# ---------------------------------------------------------------------------


@rule
class Err001SwallowedExceptions:
    id = "ERR001"
    doc = (
        "no bare `except:`; no `except Exception:` whose body only "
        "passes/continues (a silent swallow hides Byzantine-input "
        "bugs and liveness stalls)"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not (ctx.in_plane or ctx.in_transport):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield ctx.finding(
                    self.id,
                    node,
                    "bare `except:` catches SystemExit/KeyboardInterrupt "
                    "too; name the exception",
                )
                continue
            name = (
                node.type.id
                if isinstance(node.type, ast.Name)
                else getattr(node.type, "attr", None)
            )
            if name in ("Exception", "BaseException") and all(
                isinstance(s, (ast.Pass, ast.Continue)) for s in node.body
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"blanket `except {name}:` swallows the error "
                    "(body is only pass/continue); handle, log, or "
                    "narrow it",
                )


__all__ = [
    "Det001WallClockAndEntropy",
    "Det002SetIterationOrder",
    "Det003HubColumnarSeam",
    "Det005RosterVersionAccessor",
    "Conc001LockDiscipline",
    "Conc002BlockingInHandlers",
    "Err001SwallowedExceptions",
]
