"""PR 37's configuration and cell, ``n16-b4k-rejoin5.steady``: the files
load by name and are ``n16-b4k-crash5``'s but for the client rule and
what the issue names; the cell stands on every list its twin stands on;
a toy copy whose clients return when the port opens is correct, and the
restarted validators did acknowledge transactions before they were
level; the two CATCHUP readers read a number on a toy traced run of
either client rule and None where there is no fault schedule or no such
span.  (A new file beside test_crash_cell.py, whose helpers it
borrows.)"""

import json

import pytest

from benchmarks.tests.test_crash_cell import (  # noqa: F401
    SEED,
    _kept,
    add_crash_cell,
)
from benchmarks.tests.test_harness import (  # noqa: F401
    ROOT,
    harness,
    toy_root,
)

CELL = "n16-b4k-rejoin5.steady"
TWIN = "n16-b4k-crash5.steady"
READERS = ["catchup_pct", "catchup_bodies_per_adoption"]


def test_the_configuration_and_its_cell_are_the_twins_but_for_the_clients():
    from benchmarks import spec
    from benchmarks.executors import CLIENTS_RETURN, fault_schedule

    cell, twin = spec.load_cell(CELL), spec.load_cell(TWIN)
    assert cell.chips == 1
    changed = {"name", "source", "deployment", "guarantees", "assumed",
               "reduced", "cluster"}
    assert set(cell.config) == set(twin.config)
    for key in set(twin.config) - changed:
        assert cell.config[key] == twin.config[key], key
    # the cluster's keys, and what the first guarantee needs of the
    # program: a program without it refuses the configuration
    from cleisthenes_tpu.protocol.cluster import HOLDS

    assert cell.config["cluster"] == dict(
        twin.config["cluster"], requires=["requeue_at_adoption"]
    )
    assert set(cell.config["cluster"]["requires"]) <= HOLDS
    assert cell.config["guarantees"][1:] == twin.config["guarantees"][1:]
    first = cell.config["guarantees"][0]
    assert first.startswith(twin.config["guarantees"][0])
    assert first.endswith("restarted validator that was not yet level")
    assert {k for k in twin.config["assumed"]
            if cell.config["assumed"][k] != twin.config["assumed"][k]
            } == {"clients"}
    assert set(cell.config["assumed"]) == set(twin.config["assumed"]) | {
        "requires"
    }
    assert list(cell.config["reduced"]) == [
        "wal_disks", "fold", "propose_on_round",
    ]
    for key in ("wal_disks", "fold"):
        assert cell.config["reduced"][key] == twin.config["reduced"][key]
    # the traffic: value for value but the client rule
    words = {"who", "why"}
    assert set(cell.traffic) == set(twin.traffic)
    assert {k for k in set(twin.traffic) - words
            if cell.traffic[k] != twin.traffic[k]} == {"clients_return"}
    assert (twin.traffic["clients_return"], cell.traffic["clients_return"]
            ) == CLIENTS_RETURN
    ids = [f"node{i:03d}" for i in range(16)]
    assert fault_schedule(cell.traffic, ids, 5).clients_return == "at_restart"
    # BENCHMARK.json: appended, on every list the twin is on
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][-1]["name"] == "n16-b4k-rejoin5"
    assert bench["configs"][-1]["reduced"] == list(cell.config["reduced"])
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for metric in bench["end_to_end"] + bench["per_layer"]:
        lists = metric.get("workloads")
        if lists is not None:
            assert (TWIN in lists) == (CELL in lists), metric["name"]
    assert [m["name"] for m in cell.end_to_end] == [
        "settle_p50_ms", "settle_p99_ms", "order_p50_ms", "setup_s",
    ]
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in twin.per_layer
    ]
    assert [m["name"] for m in cell.per_layer[-2:]] == READERS
    for m in cell.per_layer[-2:]:
        assert m["workloads"] == [TWIN, CELL]
        assert (m["layer"], m["moves"], m["better"], m["source"]) == (
            "fault schedule", "settle_p99_ms", "lower", "program_span",
        )
        spec.load_reader(m["name"])


def _ask_what_the_file_asks(toy_root, name="toy-crash"):
    """The toy copy is made from the twin's file: give it the one key of
    ``cluster`` that the rejoin configuration adds."""
    real = json.loads(
        (ROOT / "benchmarks/configs/n16-b4k-rejoin5.json").read_text()
    )
    path = toy_root / f"benchmarks/configs/{name}.json"
    cfg = json.loads(path.read_text())
    cfg["cluster"]["requires"] = real["cluster"]["requires"]
    path.write_text(json.dumps(cfg))


def test_a_program_without_the_requeue_refuses_the_configuration(
    harness, toy_root, monkeypatch,
):
    """What PR 37's parent does with these files laid over it (there a
    TypeError: no such argument): the run ends before the keys are dealt
    and leaves no log behind."""
    from cleisthenes_tpu.protocol import cluster

    cell, _dead = add_crash_cell(
        toy_root, 4, traffic={"clients_return": "at_restart"}, rate=2000,
    )
    _ask_what_the_file_asks(toy_root)
    monkeypatch.setattr(cluster, "HOLDS", frozenset())
    with pytest.raises(ValueError, match="requeue_at_adoption"):
        harness.run_cell(cell, SEED, 3.0, False, root=toy_root)
    logs = toy_root / ".bench_wal"
    assert not logs.exists() or not list(logs.iterdir())


@pytest.mark.parametrize("clients_return", ["at_restart", "in_service"])
def test_a_toy_copy_reads_the_catchup_plane(harness, toy_root, clients_return):
    """Traced, N=4: correct under either client rule; the readers read
    the spans; with ``at_restart`` the restarted validator acknowledged
    transactions before it was level."""
    cell, dead = add_crash_cell(
        toy_root, 4, traffic={"clients_return": clients_return}, rate=2000,
    )
    _ask_what_the_file_asks(toy_root)
    kept = {}
    result = harness.run_cell(cell, SEED, 3.0, True, root=toy_root,
                              fault=_kept(kept))
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    ex = kept["executor"]
    victim = ex.ids[dead[0]]
    (o,) = ex.outages
    behind = [r for r in ex.submissions[
        ex.fault_log[1]["submissions"]:o["submissions_in_service"]]
        if r[1] == victim and r[2]]
    assert bool(behind) == (clients_return == "at_restart")
    metrics = result["metrics"]
    assert 0 < metrics["catchup_pct"]["value"] < 100
    # three peers answer every request, so at least three bodies an
    # epoch adopted
    assert metrics["catchup_bodies_per_adoption"]["value"] >= 3


def test_the_readers_find_nothing_without_a_schedule_or_without_the_spans(
    monkeypatch,
):
    from benchmarks import spec
    from benchmarks.layer_metrics import _catchup as catchup

    readers = [spec.load_reader(name) for name in READERS]
    spans = {
        "catchup/adopt": {"calls": 26, "total_s": 0.5, "self_s": 0.25},
        "catchup/serve_body": {"calls": 1144, "total_s": 2.0, "self_s": 2.0},
        "ledger/replay": {"calls": 15, "total_s": 0.75, "self_s": 0.75},
        "router/route": {"calls": 9, "total_s": 9.0, "self_s": 1.0},
    }
    monkeypatch.setattr(
        catchup, "totals",
        lambda run: dict(spans) if run.get("trace") else None,
    )
    faulted = {"trace": {"window_s": 20.0}, "faults": {"events": []}}
    assert [read(faulted) for read in readers] == [15.0, 44.0]
    # a cell without a fault schedule (the logged twin) reads nothing
    assert [read({"trace": {"window_s": 20.0}}) for read in readers] == [
        None, None,
    ]
    # nor an untraced run, which has no table at all
    assert [read({"faults": {"events": []}}) for read in readers] == [
        None, None,
    ]
    # nor a program that has none of the spans (PR 36's)
    del spans["catchup/adopt"], spans["catchup/serve_body"]
    del spans["ledger/replay"]
    assert [read(faulted) for read in readers] == [None, None]
