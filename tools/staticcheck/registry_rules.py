"""Pass 2 of the whole-program analyzer: the registry rule catalog.

These rules run over the cross-module ProgramIndex
(tools/staticcheck/program.py) rather than one file's AST, encoding
the contracts PRs 7-13 enforced by reviewer convention:

- WIRE001   payload-kind / pb-extension-tag registry integrity
- SCHEMA001 Metrics counters vs snapshot schema vs golden exposition
- ARM001    Config arm flags vs wave entry points vs perfgate
            fingerprint keys vs equivalence-test pins
- VERIFY001 (per-file) network-origin frames must pass verify_wire*
            before any handler dispatch

Deterministic, statically-checkable protocol state is the precondition
for a replayable finality argument (PAPERS.md arxiv 2512.09409) and
for batching crypto behind service seams (arxiv 2502.03247): each rule
turns one of those reviewed-by-hand contracts into a machine gate.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.staticcheck.core import FileContext, Finding, rule
from tools.staticcheck.program import (
    PB_RESERVED_TAGS,
    ProgramIndex,
)


def _program_finding(
    rule_id: str, relpath: str, line: int, message: str, ctx_map
) -> Finding:
    snippet = ""
    ctx = ctx_map.get(relpath)
    if ctx is not None:
        snippet = ctx.source_line(line)
    return Finding(
        rule=rule_id,
        path=relpath,
        line=line,
        col=0,
        message=message,
        snippet=snippet,
    )


# ---------------------------------------------------------------------------
# WIRE001: the payload-kind / pb-tag registry
# ---------------------------------------------------------------------------
#
# transport/message.py's ``_KIND_*`` discriminants and
# transport/pb_adapter.py's ``_PB_TAG_*`` extension slots were
# extended by hand four times (PRs 1/8/12); each extension had to
# re-establish, in review, that the number was fresh, that encode and
# parse both learned the kind, and that the pb adapter either carries
# it or deliberately does not (batch/bundle kinds are capabilities
# beyond the reference's oneof and stay native-only, with a pragma
# saying so).  This rule is that checklist, mechanized.

@rule
class Wire001Registry:
    id = "WIRE001"
    doc = (
        "payload kinds (_KIND_*) must carry unique numbers and "
        "encode+parse coverage, and a pb-adapter slot or a justified "
        "pragma; pb extension tags (_PB_TAG_*) must be unique, "
        "referenced, and off the reserved proto3 envelope numbers"
    )

    def check_program(
        self, index: ProgramIndex, ctx_map
    ) -> Iterator[Finding]:
        pb_by_stem: Dict[str, List] = {}
        for p in index.pb_modules:
            for stem in p.import_stems:
                pb_by_stem.setdefault(stem, []).append(p)
        for w in index.wire_modules:
            seen_value: Dict[int, str] = {}
            paired = pb_by_stem.get(w.stem, [])
            pb_kind_refs: Set[str] = set()
            for p in paired:
                pb_kind_refs |= p.kind_refs
            for name in sorted(w.kinds):
                value, line = w.kinds[name]
                other = seen_value.get(value)
                if other is not None:
                    yield _program_finding(
                        self.id, w.relpath, line,
                        f"{name} reuses payload kind number {value} "
                        f"(already taken by {other}); every oneof "
                        "discriminant must be unique",
                        ctx_map,
                    )
                else:
                    seen_value[value] = name
                if name not in w.encode_covered:
                    yield _program_finding(
                        self.id, w.relpath, line,
                        f"{name} has no encode branch (never returned "
                        "by a payload encoder); an unencodable kind "
                        "is registry dead weight or a missed case",
                        ctx_map,
                    )
                if name not in w.parse_covered:
                    yield _program_finding(
                        self.id, w.relpath, line,
                        f"{name} has no parse branch (never compared "
                        "against an incoming kind); frames of this "
                        "kind would be rejected as unknown",
                        ctx_map,
                    )
                if paired and name not in pb_kind_refs:
                    yield _program_finding(
                        self.id, w.relpath, line,
                        f"{name} has no pb-adapter slot; give it an "
                        "extension tag or pragma why the capability "
                        "stays native-only",
                        ctx_map,
                    )
        for p in index.pb_modules:
            seen_tag: Dict[int, str] = {}
            for name in sorted(p.tags):
                value, line = p.tags[name]
                other = seen_tag.get(value)
                if other is not None:
                    yield _program_finding(
                        self.id, p.relpath, line,
                        f"{name} reuses pb extension tag {value} "
                        f"(already taken by {other}); a stock decoder "
                        "cannot tell the two fields apart",
                        ctx_map,
                    )
                else:
                    seen_tag[value] = name
                if value in PB_RESERVED_TAGS:
                    yield _program_finding(
                        self.id, p.relpath, line,
                        f"{name}={value} collides with the reference "
                        "envelope's reserved tags 1-4 (signature, "
                        "timestamp, rbc, bba)",
                        ctx_map,
                    )
                if name not in p.tag_refs:
                    yield _program_finding(
                        self.id, p.relpath, line,
                        f"{name} is declared but never used by the "
                        "adapter's encode/decode paths (orphaned tag)",
                        ctx_map,
                    )


# ---------------------------------------------------------------------------
# SCHEMA001: the metrics snapshot / exposition schema
# ---------------------------------------------------------------------------
#
# The "zeroed-key snapshot schema rule" was restated in three PR
# descriptions (9/10/13): every counter the code increments must
# appear in Metrics.snapshot() (always present, zeroed without a
# provider) and its family must exist in the golden /metrics
# exposition — otherwise dashboards silently lose a signal, or the
# golden scrape test pins families the code no longer emits.

@rule
class Schema001MetricsContract:
    id = "SCHEMA001"
    doc = (
        "every Metrics counter must be incremented somewhere and read "
        "into the snapshot schema; every exposition family must exist "
        "in the golden scrape, and vice versa — no silent drift"
    )

    def check_program(
        self, index: ProgramIndex, ctx_map
    ) -> Iterator[Finding]:
        for m in index.metrics_modules:
            for attr in sorted(m.counters):
                line = m.counters[attr]
                # never-incremented is a claim about the CONSUMERS,
                # who live in other files: a lone-real-file scan has
                # no standing to convict (lint the tree)
                if (
                    not index.partial_scan
                    and index.counter_incs.get(attr, 0) == 0
                ):
                    yield _program_finding(
                        self.id, m.relpath, line,
                        f"counter {m.cls_name}.{attr} is declared but "
                        "never incremented anywhere in the scanned "
                        "tree (dead metric, or its call sites were "
                        "lost in a refactor)",
                        ctx_map,
                    )
                if attr not in m.snapshot_reads:
                    yield _program_finding(
                        self.id, m.relpath, line,
                        f"counter {m.cls_name}.{attr} never reaches "
                        "snapshot() (read self.X.value into the "
                        "schema, zeroed-key, so scrapers see it)",
                        ctx_map,
                    )
        if index.golden_families is None:
            return
        emitted: Set[str] = set()
        for e in index.expo_modules:
            emitted |= e.family_candidates
            for fam in sorted(e.families):
                if fam not in index.golden_families:
                    yield _program_finding(
                        self.id, e.relpath, e.families[fam],
                        f"exposition family {fam!r} is missing from "
                        "the golden exposition; regenerate "
                        "tests/golden/metrics_exposition.txt",
                        ctx_map,
                    )
        if index.expo_modules:
            anchor = index.expo_modules[0]
            for fam in sorted(index.golden_families - emitted):
                yield _program_finding(
                    self.id, anchor.relpath, 1,
                    f"golden exposition family {fam!r} is no longer "
                    "emitted by any scanned exposition; regenerate "
                    "the golden or restore the family",
                    ctx_map,
                )


# ---------------------------------------------------------------------------
# ARM001: arm-flag registry parity
# ---------------------------------------------------------------------------
#
# A seam that keeps a comparison arm live behind a Config flag for
# byte-equivalence must pin both values in tests, and perfgate
# fingerprints must key on the flag so a mode flip never gates against
# the other mode's trend.  ``ARM_FLAGS`` in config.py is the declared
# registry (the @guarded_by of the both-arms discipline); this rule
# cross-checks it against the Config fields, the fingerprint keys and
# the equivalence tests' explicit pins.

@rule
class Arm001WaveArmParity:
    id = "ARM001"
    doc = (
        "every ARM_FLAGS entry must be a bool or int Config field, "
        "read by the package, pinned explicitly in tests (>= 2 "
        "distinct values for int arms), and a perfgate fingerprint "
        "key"
    )

    def check_program(
        self, index: ProgramIndex, ctx_map
    ) -> Iterator[Finding]:
        if not index.config_modules:
            return
        for c in index.config_modules:
            for flag in c.arm_flags:
                is_int_arm = flag in c.int_fields
                if flag not in c.bool_fields and not is_int_arm:
                    yield _program_finding(
                        self.id, c.relpath, c.arm_flags_line,
                        f"ARM_FLAGS entry {flag!r} is not a bool or "
                        "int Config field (stale registry entry)",
                        ctx_map,
                    )
                    continue
                line = (
                    c.int_fields[flag]
                    if is_int_arm
                    else c.bool_fields[flag]
                )
                # never-read convicts the consumers; a lone-real-file
                # scan has none in view (same rule as SCHEMA001)
                if (
                    not index.partial_scan
                    and flag not in index.attr_reads
                    and flag not in index.kw_names
                ):
                    yield _program_finding(
                        self.id, c.relpath, line,
                        f"arm flag {flag!r} is never read anywhere "
                        "in the scanned tree (dead arm; nothing "
                        "selects on it)",
                        ctx_map,
                    )
                if (
                    index.fingerprint_keys is not None
                    and flag not in index.fingerprint_keys
                ):
                    yield _program_finding(
                        self.id, c.relpath, line,
                        f"arm flag {flag!r} is not a perfgate "
                        "fingerprint key; a mode flip would gate "
                        "against the other mode's trend records",
                        ctx_map,
                    )
                if index.test_flag_pins is None:
                    continue
                if is_int_arm:
                    # an int arm (Config.lanes) needs the baseline
                    # value AND a fast-path value pinned, or the
                    # byte-equivalence comparison never runs
                    if len(index.int_flag_pin_values(flag)) < 2:
                        yield _program_finding(
                            self.id, c.relpath, line,
                            f"int arm flag {flag!r} pins fewer than "
                            "2 distinct values in tests; both the "
                            "byte-equivalence baseline and the fast "
                            "arm need explicit coverage",
                            ctx_map,
                        )
                elif not index.flag_pinned_in_tests(flag):
                    yield _program_finding(
                        self.id, c.relpath, line,
                        f"arm flag {flag!r} is never pinned "
                        "(flag=True/False) in tests; the "
                        "byte-equivalence comparison arm has no "
                        "coverage",
                        ctx_map,
                    )


# ---------------------------------------------------------------------------
# VERIFY001: network-origin frames verify before dispatch (per-file)
# ---------------------------------------------------------------------------
#
# Every inbound path does decode -> verify_wire* -> handler dispatch;
# the MAC check is the only thing standing between a Byzantine peer's
# bytes and the protocol state machines.  This light intraprocedural
# taint walk flags any function in transport/ that decodes a wire
# frame (decode_frame / decode_frame_shared / decode_message /
# decode_pb_message) and lets a value derived from it reach a handler
# sink (serve_request / serve_wave / handle_message) without an
# intervening verify_wire* call over it.  Sanctioned unverified paths
# (none today) would carry allow[VERIFY001] pragmas with
# justifications.

_VERIFY001_SOURCES = frozenset(
    (
        "decode_frame",
        "decode_frame_shared",
        "decode_message",
        "decode_pb_message",
    )
)
_VERIFY001_SINKS = frozenset(
    ("serve_request", "serve_wave", "handle_message")
)


def _call_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _names_of(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _target_names(target: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name):
            out.add(n.id)
    return out


@rule
class Verify001FrameTaint:
    id = "VERIFY001"
    doc = (
        "in transport/ code, a decoded wire frame must pass "
        "verify_wire* before reaching a handler dispatch "
        "(serve_request/serve_wave/handle_message) in the same "
        "function"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not ctx.in_transport:
            return
        for fn in ast.walk(ctx.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._walk_function(ctx, fn)

    def _walk_function(
        self, ctx: FileContext, fn: ast.AST
    ) -> Iterator[Finding]:
        tainted: Set[str] = set()
        findings: List[Finding] = []

        def contains_source(node: ast.AST) -> bool:
            for n in ast.walk(node):
                if isinstance(n, ast.Call):
                    name = _call_name(n)
                    if name in _VERIFY001_SOURCES:
                        return True
            return False

        def is_tainted(node: ast.AST) -> bool:
            return bool(_names_of(node) & tainted)

        def handle_call(node: ast.Call) -> None:
            name = _call_name(node)
            if name is None:
                return
            if name.startswith("verify"):
                # verification sanitizes every name it was handed
                for arg in list(node.args) + [
                    kw.value for kw in node.keywords
                ]:
                    tainted.difference_update(_names_of(arg))
                return
            if name == "append":
                # L.append(tainted) taints the collection
                val_tainted = any(
                    is_tainted(a) for a in node.args
                )
                if val_tainted and isinstance(
                    node.func, ast.Attribute
                ):
                    tainted.update(_names_of(node.func.value))
                return
            if name in _VERIFY001_SINKS:
                for arg in list(node.args) + [
                    kw.value for kw in node.keywords
                ]:
                    if is_tainted(arg):
                        findings.append(
                            ctx.finding(
                                self.id,
                                node,
                                f"{name}() dispatches a frame "
                                "decoded in this function with no "
                                "verify_wire* between decode and "
                                "dispatch; Byzantine bytes reach "
                                "the protocol plane unauthenticated",
                            )
                        )
                        break

        def assign(targets: List[ast.AST], value: ast.AST) -> None:
            make_tainted = contains_source(value) or is_tainted(value)
            for t in targets:
                names = _target_names(t)
                if make_tainted:
                    tainted.update(names)
                else:
                    tainted.difference_update(names)

        def visit(node: ast.AST) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return  # nested functions analyzed on their own
            if isinstance(node, ast.Assign):
                # calls inside the value run first (decode itself)
                for child in ast.walk(node.value):
                    if isinstance(child, ast.Call):
                        handle_call(child)
                assign(node.targets, node.value)
                return
            if isinstance(node, ast.AnnAssign) and node.value is not None:
                assign([node.target], node.value)
                return
            if isinstance(node, (ast.For, ast.AsyncFor)):
                assign([node.target], node.iter)
                for child in node.body + node.orelse:
                    visit(child)
                return
            if isinstance(node, ast.Call):
                handle_call(node)
                for child in ast.iter_child_nodes(node):
                    visit(child)
                return
            for child in ast.iter_child_nodes(node):
                visit(child)

        for stmt in fn.body:
            visit(stmt)
        yield from findings


__all__ = [
    "Arm001WaveArmParity",
    "Schema001MetricsContract",
    "Verify001FrameTaint",
    "Wire001Registry",
]
