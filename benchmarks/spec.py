"""Where the harness finds a cell's files: everything by the names in
BENCHMARK.json, so that a later PR adds a configuration, a cell or a
per-layer metric by adding files and entries and edits none.

    workload  -> its entry in BENCHMARK.json["workloads"]
    config    -> BENCHMARK.json["configs"][...]["file"]
    traffic   -> <dir of run.py>/traffic/<workload name>.json
    metric    -> <dir of run.py>/layer_metrics/<metric name>.py
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names does not say what a run needs."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict  # the configuration's file, parsed
    traffic: Dict  # the cell's traffic file, parsed
    end_to_end: List[Dict]  # the metrics this cell reports, --trace 0
    per_layer: List[Dict]  # and --trace 1
    root: pathlib.Path = ROOT  # the checkout: what a run writes goes under it


def _read_json(path: pathlib.Path) -> Dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError) as exc:
        raise SpecError(f"cannot read {path}: {exc}") from exc


def _bench_dir(root: pathlib.Path) -> pathlib.Path:
    """This directory, in the checkout at ``root``."""
    return root / HERE.relative_to(ROOT)


def _listed(metric: Dict, cell: str, reported: Optional[set]) -> bool:
    """Does ``metric`` belong to ``cell``?  By its ``workloads`` list;
    a per-layer metric without one follows the end-to-end metric it
    moves (``reported`` = the end-to-end names of this cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    rows = [w for w in bench["workloads"] if w["name"] == workload]
    if not rows:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no workload {workload!r}; BENCHMARK.json has {names}")
    row = rows[0]
    cfg_rows = [c for c in bench["configs"] if c["name"] == row["config"]]
    if not cfg_rows:
        raise SpecError(f"workload {workload} names no known config")
    config = _read_json(root / cfg_rows[0]["file"])
    traffic = _read_json(_bench_dir(root) / "traffic" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _listed(m, workload, None)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _listed(m, workload, reported)]
    return Cell(
        name=workload,
        chips=int(row["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=layer,
        root=root,
    )


def load_reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    """The per-layer metric's reader: ``read(run) -> float | None`` in
    ``layer_metrics/<name>.py``.  None means it found nothing to read,
    and the harness leaves the metric out of the line."""
    path = _bench_dir(root) / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {name} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_layer_metric_" + "".join(
            ch if ch.isalnum() else "_" for ch in name
        ),
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


__all__ = ["Cell", "SpecError", "load_cell", "load_reader", "HERE", "ROOT"]
