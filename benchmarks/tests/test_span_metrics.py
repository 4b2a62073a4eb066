"""The readers of the program's span totals (layer_metrics/_spans.py):
each on a made-up table and run, the cases that read nothing, and the
entries of BENCHMARK.json that name them."""

import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

TOTALS = {
    # name: (calls, total_s, self_s)
    "lockstep/epoch": (2, 2.0, 0.1),
    "lockstep/bba": (2, 1.6, 0.4),
    "tpke/issue_batch": (8, 0.9, 0.2),
    "tpke/cp_challenge": (16, 0.1, 0.1),
    "ops/modexp_12x22.comb": (8, 0.6, 0.02),
    "ops/pack": (8, 0.1, 0.1),
    "ops/device": (8, 0.4, 0.4),
    "ops/unpack": (8, 0.08, 0.08),
    "ops/host": (3, 0.05, 0.05),
    "hb/on_idle": (40, 0.5, 0.06),
    "coin/issue_batch": (40, 0.1, 0.04),
    "settle/combine": (64, 0.02, 0.02),
    "rbc/propose": (16, 0.03, 0.03),
    "hub/flush": (10, 0.3, 0.12),
    "hub/shares": (10, 0.1, 0.03),
    "transport/step_wave": (30, 0.7, 0.05),
    "transport/frame_decode": (400, 0.2, 0.2),
    "router/route": (400, 0.3, 0.25),
    "ingress/submit": (5000, 0.05, 0.05),
}
WINDOW_S = 2.5
WANT = {
    "ops_marshal_pct": 100 * (0.1 + 0.08) / WINDOW_S,
    "ops_device_wait_pct": 100 * 0.4 / WINDOW_S,
    "ops_host_kernel_pct": 100 * 0.05 / WINDOW_S,
    "tpke_host_pct": 100 * (0.2 + 0.1) / WINDOW_S,
    "lockstep_host_pct": 100 * (0.1 + 0.4) / WINDOW_S,
    "hb_turn_pct": 100 * (0.06 + 0.04 + 0.02 + 0.03) / WINDOW_S,
    "hub_self_pct": 100 * (0.12 + 0.03) / WINDOW_S,
    "codec_mac_pct": 100 * (0.05 + 0.2) / WINDOW_S,
    "router_banks_pct": 100 * 0.25 / WINDOW_S,
    "span_coverage_pct": 100 * sum(r[2] for r in TOTALS.values()) / WINDOW_S,
    "ingress_submit_span_us": 1e6 * 0.05 / 5000,
}


def _table():
    return {
        name: {"calls": c, "total_s": t, "self_s": s}
        for name, (c, t, s) in TOTALS.items()
    }


@pytest.fixture
def program_totals(monkeypatch):
    from cleisthenes_tpu.utils import trace

    table = {"rows": _table()}
    monkeypatch.setattr(
        trace, "totals", lambda: dict(table["rows"]), raising=False
    )
    return table


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_a_made_up_table(metric, program_totals):
    from benchmarks import spec

    read = spec.load_reader(metric)
    run = {"trace": {"window_s": WINDOW_S, "busy_s": 0.3, "programs": {}}}
    assert read(run) == pytest.approx(WANT[metric])
    # no trace: nothing to divide by, and the table is not the window's
    assert read({"trace": None}) is None and read({}) is None
    # a program that took no such span reads 0 (the mean of none: nothing)
    program_totals["rows"] = {"epoch/other": _table()["ops/host"]}
    if metric == "ingress_submit_span_us":
        assert read(run) is None
    elif metric == "span_coverage_pct":
        assert read(run) == pytest.approx(100 * 0.05 / WINDOW_S)
    else:
        assert read(run) == 0.0
    # and one that took none at all leaves the metric out
    program_totals["rows"] = {}
    assert read(run) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_a_program_without_the_table(metric, monkeypatch):
    """The parent commit: utils/trace.py is there, totals() is not."""
    from benchmarks import spec
    from cleisthenes_tpu.utils import trace

    monkeypatch.delattr(trace, "totals", raising=False)
    run = {"trace": {"window_s": WINDOW_S}}
    assert spec.load_reader(metric)(run) is None


def test_entries_have_readers_and_cells_that_exist():
    from benchmarks import spec

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(WANT) <= set(entries)
    for name in WANT:
        entry = entries[name]
        assert entry["source"] == "program_span"
        assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
        assert callable(spec.load_reader(name))
        for cell in entry["workloads"]:
            reported = {m["name"] for m in spec.load_cell(cell).end_to_end}
            assert entry["moves"] in reported
    assert entries["span_coverage_pct"]["better"] == "higher"
    assert entries["ingress_submit_span_us"]["unit"] == "us"
    # entries are only ever appended: PR 25's ten come first, then these
    assert list(entries)[10:10 + len(WANT)] == [
        "ops_marshal_pct", "ops_device_wait_pct", "ops_host_kernel_pct",
        "tpke_host_pct", "lockstep_host_pct", "hb_turn_pct", "hub_self_pct",
        "codec_mac_pct", "router_banks_pct", "span_coverage_pct",
        "ingress_submit_span_us",
    ]
