"""Shared file-walking + reporting helpers for the stdlib lint gates.

Both gate tools — tools/format_gate.py (style invariants) and
tools/staticcheck (the determinism-plane AST analyzer) — walk the same
tree and report the same way: one ``path:line: message`` line per
problem plus a one-line summary, exit 1 on any problem.  This module
is that shared substrate, so the two gates can never drift apart on
WHAT they scan or HOW they report.
"""

from __future__ import annotations

import pathlib
from typing import Iterable, List, Sequence

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def walk_python_files(target: pathlib.Path) -> List[pathlib.Path]:
    """Every .py file under ``target`` (or the file itself), sorted
    for deterministic gate output; silently empty for missing paths
    (optional entry scripts)."""
    if not target.exists():
        return []
    if target.is_file():
        return [target] if target.suffix == ".py" else []
    return sorted(
        p for p in target.rglob("*.py") if "__pycache__" not in p.parts
    )


def gate_targets(root: pathlib.Path = REPO_ROOT) -> List[pathlib.Path]:
    """The full file set both repo gates check: the package, the test
    suite, the tools themselves, and the entry scripts."""
    out: List[pathlib.Path] = []
    for rel in ("cleisthenes_tpu", "tests", "tools"):
        out.extend(walk_python_files(root / rel))
    for rel in ("bench.py", "chip_smoke.py", "__graft_entry__.py", "demo.py"):
        out.extend(walk_python_files(root / rel))
    return out


def rel_posix(path: pathlib.Path, root: pathlib.Path = REPO_ROOT) -> str:
    """Repo-relative posix path — the canonical spelling in findings,
    baselines and reports (stable across platforms)."""
    try:
        return path.resolve().relative_to(root).as_posix()
    except ValueError:
        return path.as_posix()


def report(
    name: str,
    n_files: int,
    problems: Sequence[str],
    extra: Iterable[str] = (),
) -> int:
    """Print problems + the gate summary line; return the exit code."""
    for p in problems:
        print(p)
    for line in extra:
        print(line)
    print(f"{name}: {n_files} files, {len(problems)} problem(s)")
    return 1 if problems else 0


__all__ = [
    "REPO_ROOT",
    "walk_python_files",
    "gate_targets",
    "rel_posix",
    "report",
]
