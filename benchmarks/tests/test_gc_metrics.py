"""The collector's readers (layer_metrics/_gc.py): ``gc_pct`` and its two
twins, ``gc_full_per_epoch``; each on a made-up table and run, the
cases that read nothing, and the entries of BENCHMARK.json that name
them."""

import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
WINDOW_S = 2.5
SATURATED = [
    "n16-b4k.saturated", "n128-b10k-lockstep.saturated", "n64-b10k.saturated",
    "n128-b10k-mesh4.saturated", "n16-b16k-tx250.saturated",
]
CELLS = {
    "gc_pct": ("settled_tx_per_s", SATURATED),
    "gc_pct.lat": ("settle_p90_ms", ["n16-b4k.steady", "n16-b4k-wal.steady"]),
    "gc_pct.crash": (
        "settle_p99_ms", ["n16-b4k-crash5.steady", "n16-b4k-rejoin5.steady"],
    ),
    "gc_full_per_epoch": ("settled_tx_per_s", SATURATED),
}
SHARES = ("gc_pct", "gc_pct.lat", "gc_pct.crash")


def _row(calls, total_s, self_s):
    return {"calls": calls, "total_s": total_s, "self_s": self_s}


@pytest.fixture
def program_totals(monkeypatch):
    from cleisthenes_tpu.utils import trace

    table = {"rows": {
        "router/route": _row(400, 0.9, 0.6),
        "gc/gen0": _row(300, 0.03, 0.03),
        "gc/gen1": _row(25, 0.04, 0.04),
        "gc/gen2": _row(3, 0.3, 0.3),
    }}
    monkeypatch.setattr(
        trace, "totals", lambda: dict(table["rows"]), raising=False
    )
    return table


def _run(epochs_before=10, epochs_after=16):
    return {
        "trace": {"window_s": WINDOW_S, "busy_s": 0.01, "programs": {}},
        "counters": {"trace": {
            "before": {"epochs": epochs_before},
            "after": {"epochs": epochs_after},
        }},
    }


@pytest.mark.parametrize("metric", SHARES)
def test_share_of_the_traced_window(metric, program_totals):
    from benchmarks import spec

    read = spec.load_reader(metric)
    assert read(_run()) == pytest.approx(100 * (0.03 + 0.04 + 0.3) / WINDOW_S)
    # no trace: nothing to divide by
    assert read({"trace": None, "counters": {}}) is None
    # the parent commit's program: spans, none of them the collector's
    program_totals["rows"] = {"router/route": _row(400, 0.9, 0.9)}
    assert read(_run()) is None


def test_full_collections_per_epoch(program_totals):
    from benchmarks import spec

    read = spec.load_reader("gc_full_per_epoch")
    assert read(_run(10, 16)) == pytest.approx(3 / 6)
    assert read(_run(10, 10)) is None  # no epoch settled in the trace
    run = _run()
    run["counters"]["trace"] = {}  # the trace never started
    assert read(run) is None
    # collections, none of them full: 0, not nothing
    del program_totals["rows"]["gc/gen2"]
    assert read(_run()) == 0.0
    program_totals["rows"] = {"router/route": _row(400, 0.9, 0.9)}
    assert read(_run()) is None


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_reader_on_a_program_without_the_table(metric, monkeypatch):
    from benchmarks import spec
    from cleisthenes_tpu.utils import trace

    monkeypatch.delattr(trace, "totals")
    assert spec.load_reader(metric)(_run()) is None


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_entry_stands_on_its_cells(metric):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    moves, cells = CELLS[metric]
    assert entry["moves"] == moves
    assert entry["workloads"] == cells
    assert entry["layer"] == "Python collector"
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(e2e["workloads"])
    assert (ROOT / "benchmarks" / "layer_metrics" / f"{metric}.py").is_file()
