"""Pass 1 of the whole-program analyzer: the cross-module registry
index.

The per-file rules (tools/staticcheck/rules.py) see one AST at a time;
the invariants that actually broke ground in PRs 7-13 are CROSS-MODULE
contracts: payload kinds must carry encode+parse+pb coverage
(transport/message.py vs transport/pb_adapter.py), Metrics counters
must appear in the snapshot schema and the golden /metrics exposition,
and Config arm flags must be perfgate fingerprint keys with both
arms pinned in the equivalence tests.  This module builds the one index
those registry rules (tools/staticcheck/registry_rules.py) run over.

Role detection is STRUCTURAL, not path-hardcoded, so the fixture
corpus can stand up miniature registries:

- wire module    -- module-level ``_KIND_*`` int assignments
- pb adapter     -- module-level ``_PB_TAG_*`` int assignments;
                    paired to the wire module whose stem it imports
- metrics module -- a class with ``self.X = Counter()`` attributes
                    AND a ``snapshot`` method
- exposition     -- ``.family("name", ...)`` literal calls
- config module  -- ``class Config`` plus a module-level ``ARM_FLAGS``
                    declaration (the arm registry, analogous to
                    ``@guarded_by`` for CONC001)
- perfgate       -- a dict literal carrying a ``"fingerprint"`` key

Out-of-scan context is AUGMENTED from the repo root exactly when the
scanned registry is the real one (its path is not under a
``staticcheck_fixtures`` directory): the perfgate fingerprint keys
from ``tools/perfgate.py``, the arm-flag pins from ``tests/``, and the
golden exposition families from
``tests/golden/metrics_exposition.txt``.  A fixture tree provides its
own minis under its own root and gets the same treatment.
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Set, Tuple

from tools.lintcommon import rel_posix
from tools.staticcheck.core import FIXTURE_DIR_NAME

_KIND_RE = re.compile(r"^_KIND_[A-Z0-9_]+$")
_PB_TAG_RE = re.compile(r"^_PB_TAG_[A-Z0-9_]+$")
# proto3 envelope fields (signature=1, timestamp=2) + the reference
# oneof (rbc=3, bba=4): an extension tag landing on these corrupts
# stock-decoder interop
PB_RESERVED_TAGS = frozenset((1, 2, 3, 4))

_BOOL_FLAG_PIN_RE = r"\b{flag}\s*=\s*(?:True|False)\b"
# int-valued arms (Config.lanes): the pin is a literal integer, and
# the rule wants the DISTINCT values (baseline=1 vs shard-out>1)
_INT_FLAG_PIN_RE = r"\b{flag}\s*=\s*(\d+)"


def is_fixture_path(relpath: str) -> bool:
    return FIXTURE_DIR_NAME in relpath.split("/")


@dataclasses.dataclass
class WireModule:
    """One payload-kind registry (transport/message.py shaped)."""

    relpath: str
    stem: str
    kinds: Dict[str, Tuple[int, int]]  # name -> (value, line)
    encode_covered: Set[str]  # _KIND_ names appearing in a return
    parse_covered: Set[str]  # _KIND_ names appearing in a comparison


@dataclasses.dataclass
class PbModule:
    """One pb extension-tag registry (transport/pb_adapter.py shaped)."""

    relpath: str
    tags: Dict[str, Tuple[int, int]]  # name -> (value, line)
    tag_refs: Set[str]  # _PB_TAG_ names loaded (used) anywhere
    kind_refs: Set[str]  # _KIND_ names loaded anywhere
    import_stems: Set[str]  # last components of from-import modules


@dataclasses.dataclass
class MetricsModule:
    """One metrics registry: Counter attrs + the snapshot schema."""

    relpath: str
    cls_name: str
    counters: Dict[str, int]  # attr -> declaration line
    snapshot_reads: Set[str]  # attrs read as self.X.value in snapshot


@dataclasses.dataclass
class ExpoModule:
    """One Prometheus exposition: .family("name", ...) literal calls.

    ``families`` is the PRECISE set (literal first args — the anchor
    for "missing from golden" findings); ``family_candidates`` adds
    every string that is the first element of a tuple literal, because
    the exposition drives family loops off tuple tables — an
    over-approximation that is only used to witness that a golden
    family is still emitted (recall side), never to accuse."""

    relpath: str
    families: Dict[str, int]  # family name -> first call line
    family_candidates: Set[str]


@dataclasses.dataclass
class ConfigModule:
    """One arm-flag registry: Config bool/int fields + ARM_FLAGS."""

    relpath: str
    bool_fields: Dict[str, int]  # field -> line
    int_fields: Dict[str, int]  # field -> line (int-valued arms)
    arm_flags: List[str]
    arm_flags_line: int


@dataclasses.dataclass
class ProgramIndex:
    """Everything pass 2's registry rules read."""

    wire_modules: List[WireModule]
    pb_modules: List[PbModule]
    metrics_modules: List[MetricsModule]
    expo_modules: List[ExpoModule]
    config_modules: List[ConfigModule]
    counter_incs: Dict[str, int]  # counter attr -> inc() sites seen
    attr_reads: Set[str]  # every Attribute attr loaded anywhere
    kw_names: Set[str]  # every keyword-argument name used anywhere
    fingerprint_keys: Optional[Set[str]]  # None: no perfgate in sight
    golden_families: Optional[Set[str]]  # None: no golden in sight
    test_flag_pins: Optional[str]  # concatenated tests text, or None
    # True when the scan is a lone real (non-fixture) file: the
    # consumer universe is NOT in view, so absence-based accusations
    # ("never incremented", "never read", wave-unreachable) must not
    # convict — lint the tree for those.  Self-contained fixture
    # files keep the full rule set.
    partial_scan: bool = False

    def flag_pinned_in_tests(self, flag: str) -> bool:
        if self.test_flag_pins is None:
            return False
        return (
            re.search(
                _BOOL_FLAG_PIN_RE.format(flag=re.escape(flag)),
                self.test_flag_pins,
            )
            is not None
        )

    def int_flag_pin_values(self, flag: str) -> Set[int]:
        """Distinct integer literals tests pin the flag to.  An
        int-valued arm (Config.lanes) needs >= 2 of them: the
        byte-equivalence baseline value AND a shard-out value, or the
        fast arm has no equivalence coverage."""
        if self.test_flag_pins is None:
            return set()
        return {
            int(m)
            for m in re.findall(
                _INT_FLAG_PIN_RE.format(flag=re.escape(flag)),
                self.test_flag_pins,
            )
        }


# ---------------------------------------------------------------------------
# per-file extraction
# ---------------------------------------------------------------------------


def _module_int_consts(tree: ast.AST, pattern) -> Dict[str, Tuple[int, int]]:
    out: Dict[str, Tuple[int, int]] = {}
    for node in ast.iter_child_nodes(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and pattern.match(tgt.id)):
            continue
        if isinstance(node.value, ast.Constant) and isinstance(
            node.value.value, int
        ):
            out[tgt.id] = (node.value.value, node.lineno)
    return out


def _names_in(node: ast.AST, pattern) -> Set[str]:
    return {
        n.id
        for n in ast.walk(node)
        if isinstance(n, ast.Name) and pattern.match(n.id)
    }


def _extract_wire(ctx) -> Optional[WireModule]:
    kinds = _module_int_consts(ctx.tree, _KIND_RE)
    if not kinds:
        return None
    encode_covered: Set[str] = set()
    parse_covered: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Return) and node.value is not None:
            encode_covered |= _names_in(node.value, _KIND_RE)
        elif isinstance(node, ast.Compare):
            parse_covered |= _names_in(node, _KIND_RE)
    return WireModule(
        relpath=ctx.relpath,
        stem=pathlib.PurePosixPath(ctx.relpath).stem,
        kinds=kinds,
        encode_covered=encode_covered,
        parse_covered=parse_covered,
    )


def _extract_pb(ctx) -> Optional[PbModule]:
    tags = _module_int_consts(ctx.tree, _PB_TAG_RE)
    if not tags:
        return None
    tag_refs: Set[str] = set()
    kind_refs: Set[str] = set()
    import_stems: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if _PB_TAG_RE.match(node.id):
                tag_refs.add(node.id)
            elif _KIND_RE.match(node.id):
                kind_refs.add(node.id)
        elif isinstance(node, ast.ImportFrom) and node.module:
            import_stems.add(node.module.rsplit(".", 1)[-1])
    return PbModule(
        relpath=ctx.relpath,
        tags=tags,
        tag_refs=tag_refs,
        kind_refs=kind_refs,
        import_stems=import_stems,
    )


def _self_attr_of(node: ast.AST) -> Optional[str]:
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _extract_metrics(ctx) -> List[MetricsModule]:
    out: List[MetricsModule] = []
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        counters: Dict[str, int] = {}
        snapshot_fn = None
        for meth in cls.body:
            if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if meth.name == "snapshot":
                snapshot_fn = meth
            for node in ast.walk(meth):
                if (
                    isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Name)
                    and node.value.func.id == "Counter"
                ):
                    for tgt in node.targets:
                        attr = _self_attr_of(tgt)
                        if attr is not None:
                            counters[attr] = node.lineno
        if not counters or snapshot_fn is None:
            continue
        reads: Set[str] = set()
        for node in ast.walk(snapshot_fn):
            # self.<attr>.value
            if isinstance(node, ast.Attribute) and node.attr == "value":
                inner = _self_attr_of(node.value)
                if inner is not None:
                    reads.add(inner)
        out.append(
            MetricsModule(
                relpath=ctx.relpath,
                cls_name=cls.name,
                counters=counters,
                snapshot_reads=reads,
            )
        )
    return out


def _extract_expo(ctx) -> Optional[ExpoModule]:
    families: Dict[str, int] = {}
    candidates: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "family"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            families.setdefault(node.args[0].value, node.lineno)
        elif isinstance(node, ast.Tuple) and node.elts:
            first = node.elts[0]
            if isinstance(first, ast.Constant) and isinstance(
                first.value, str
            ):
                candidates.add(first.value)
    if not families:
        return None
    return ExpoModule(
        relpath=ctx.relpath,
        families=families,
        family_candidates=candidates | set(families),
    )


def _bool_annotation(ann: Optional[ast.AST]) -> bool:
    return isinstance(ann, ast.Name) and ann.id == "bool"


def _int_annotation(ann: Optional[ast.AST]) -> bool:
    return isinstance(ann, ast.Name) and ann.id == "int"


def _extract_config(ctx) -> Optional[ConfigModule]:
    cls = None
    for node in ast.iter_child_nodes(ctx.tree):
        if isinstance(node, ast.ClassDef) and node.name == "Config":
            cls = node
            break
    if cls is None:
        return None
    arm_flags: Optional[List[str]] = None
    arm_line = 0
    for node in ast.iter_child_nodes(ctx.tree):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "ARM_FLAGS"
            and isinstance(node.value, (ast.Tuple, ast.List))
        ):
            arm_flags = [
                e.value
                for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            ]
            arm_line = node.lineno
    if arm_flags is None:
        return None
    bool_fields: Dict[str, int] = {}
    int_fields: Dict[str, int] = {}
    for node in cls.body:
        if not (
            isinstance(node, ast.AnnAssign)
            and isinstance(node.target, ast.Name)
        ):
            continue
        if _bool_annotation(node.annotation):
            bool_fields[node.target.id] = node.lineno
        elif _int_annotation(node.annotation):
            int_fields[node.target.id] = node.lineno
    return ConfigModule(
        relpath=ctx.relpath,
        bool_fields=bool_fields,
        int_fields=int_fields,
        arm_flags=arm_flags,
        arm_flags_line=arm_line,
    )


def _fingerprint_keys_from_tree(tree: ast.AST) -> Optional[Set[str]]:
    """Union of literal keys across every dict that appears as the
    value of a ``"fingerprint"`` key (perfgate emits more than one
    record kind; the mini-bench fingerprint carries the arm flags)."""
    keys: Set[str] = set()
    saw = False
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        for k, v in zip(node.keys, node.values):
            if (
                isinstance(k, ast.Constant)
                and k.value == "fingerprint"
                and isinstance(v, ast.Dict)
            ):
                saw = True
                keys |= {
                    kk.value
                    for kk in v.keys
                    if isinstance(kk, ast.Constant)
                    and isinstance(kk.value, str)
                }
    return keys if saw else None


def parse_golden_families(text: str) -> Set[str]:
    """Family names from ``# TYPE <prefix>_<family> <kind>`` headers,
    with the one-segment metric prefix stripped (the exposition's
    ``family()`` names are prefix-free)."""
    out: Set[str] = set()
    for line in text.splitlines():
        if not line.startswith("# TYPE "):
            continue
        parts = line.split()
        if len(parts) >= 3 and "_" in parts[2]:
            out.add(parts[2].split("_", 1)[1])
    return out


# ---------------------------------------------------------------------------
# the index builder
# ---------------------------------------------------------------------------


def build_index(ctxs, root: pathlib.Path) -> ProgramIndex:
    wire_modules: List[WireModule] = []
    pb_modules: List[PbModule] = []
    metrics_modules: List[MetricsModule] = []
    expo_modules: List[ExpoModule] = []
    config_modules: List[ConfigModule] = []
    counter_incs: Dict[str, int] = {}
    attr_reads: Set[str] = set()
    kw_names: Set[str] = set()
    # (relpath, keys) per file carrying a "fingerprint" dict: the
    # REAL registry (a file named perfgate.py) wins over
    # fingerprint-shaped dict literals in tests/helpers, so a key
    # dropped from the real fingerprint cannot be masked by a test
    # fixture that still spells it
    fingerprints_by_file: List[Tuple[str, Set[str]]] = []

    for ctx in ctxs:
        w = _extract_wire(ctx)
        if w is not None:
            wire_modules.append(w)
        p = _extract_pb(ctx)
        if p is not None:
            pb_modules.append(p)
        metrics_modules.extend(_extract_metrics(ctx))
        e = _extract_expo(ctx)
        if e is not None:
            expo_modules.append(e)
        c = _extract_config(ctx)
        if c is not None:
            config_modules.append(c)
        fp = _fingerprint_keys_from_tree(ctx.tree)
        if fp is not None:
            fingerprints_by_file.append((ctx.relpath, fp))

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute):
                attr_reads.add(node.attr)
            elif isinstance(node, ast.keyword) and node.arg:
                kw_names.add(node.arg)
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "inc"
                and isinstance(node.func.value, ast.Attribute)
            ):
                attr = node.func.value.attr
                counter_incs[attr] = counter_incs.get(attr, 0) + 1

    # -- root augmentation (real registries only; fixture trees carry
    # their own minis under their own root) ----------------------------
    has_real_config = any(
        not is_fixture_path(c.relpath) for c in config_modules
    )
    has_real_expo = any(
        not is_fixture_path(e.relpath) for e in expo_modules
    )
    scanned = {ctx.relpath for ctx in ctxs}

    # perfgate.py-named registries beat incidental fingerprint-shaped
    # literals (e.g. perfgate's own tests building mini records)
    real_fps = [
        keys
        for relpath, keys in fingerprints_by_file
        if pathlib.PurePosixPath(relpath).name == "perfgate.py"
    ]
    pool = real_fps if real_fps else [k for _, k in fingerprints_by_file]
    fingerprint_keys: Optional[Set[str]] = None
    for keys in pool:
        fingerprint_keys = (fingerprint_keys or set()) | keys

    if fingerprint_keys is None and has_real_config:
        pg = root / "tools" / "perfgate.py"
        if pg.exists() and "tools/perfgate.py" not in scanned:
            try:
                fingerprint_keys = _fingerprint_keys_from_tree(
                    ast.parse(pg.read_text(encoding="utf-8"))
                )
            except (OSError, SyntaxError):
                fingerprint_keys = None

    test_flag_pins: Optional[str] = None
    if has_real_config:
        chunks: List[str] = []
        tests_dir = root / "tests"
        if tests_dir.is_dir():
            for py in sorted(tests_dir.glob("test_*.py")):
                if rel_posix(py, root) in scanned:
                    continue  # already parsed as a context
                try:
                    chunks.append(py.read_text(encoding="utf-8"))
                except OSError:
                    continue
        # scanned tests (a fixture tree's tests/ live under its root)
        for ctx in ctxs:
            if ctx.relpath.startswith("tests/"):
                chunks.append(ctx.text)
        if chunks:
            test_flag_pins = "\n".join(chunks)

    golden_families: Optional[Set[str]] = None
    if has_real_expo:
        golden = root / "tests" / "golden" / "metrics_exposition.txt"
        if golden.exists():
            try:
                golden_families = parse_golden_families(
                    golden.read_text(encoding="utf-8")
                )
            except OSError:
                golden_families = None

    return ProgramIndex(
        wire_modules=wire_modules,
        pb_modules=pb_modules,
        metrics_modules=metrics_modules,
        expo_modules=expo_modules,
        config_modules=config_modules,
        counter_incs=counter_incs,
        attr_reads=attr_reads,
        kw_names=kw_names,
        fingerprint_keys=fingerprint_keys,
        golden_families=golden_families,
        test_flag_pins=test_flag_pins,
        partial_scan=(
            len(ctxs) == 1 and not is_fixture_path(ctxs[0].relpath)
        ),
    )


__all__ = [
    "PB_RESERVED_TAGS",
    "ConfigModule",
    "ExpoModule",
    "MetricsModule",
    "PbModule",
    "ProgramIndex",
    "WireModule",
    "build_index",
    "is_fixture_path",
    "parse_golden_families",
]
