"""PR 29's four-chip cell, ``n128-b10k-mesh4.saturated``: its files load
by name, and a toy-size copy runs on four virtual devices (a new file
beside test_harness.py, whose fixtures and helpers it borrows: a PR that
adds a cell edits no file the benchmark already has)."""

import pytest

from benchmarks.tests.test_harness import (  # noqa: F401
    add_cell,
    add_config,
    harness,
    toy_root,
)


def test_mesh4_cell_loads_and_a_toy_copy_runs_sharded(
    harness, toy_root, monkeypatch
):
    """PR 29's four-chip cell: its configuration, traffic and the two
    mesh metrics load by name; a toy-size copy on four virtual devices
    is correct, shards its device batches (the comb among them) and
    reports both metrics.  A program whose tally and spans know no
    mesh (the parent's) reads nothing, and does not raise."""
    import jax

    from benchmarks import spec
    from cleisthenes_tpu.ops.merkle import XlaMerkle
    from cleisthenes_tpu.ops.modmath import ModEngine
    from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell = spec.load_cell("n128-b10k-mesh4.saturated")
    assert cell.chips == 4 and cell.config["chips"] == 4
    assert cell.config["config"] == {
        "n": 128, "batch_size": 10000, "crypto_backend": "tpu",
        "mesh_shape": [2, 2],
    }
    assert sorted(cell.config["reduced"]) == ["transport", "wal_dir"]
    one_chip = spec.load_cell("n128-b10k-lockstep.saturated")
    assert cell.config["guarantees"] == one_chip.config["guarantees"]
    same = ("loop", "population", "arrival_alpha", "fee_alpha", "shape_seed")
    assert {k: cell.traffic[k] for k in same} == {
        k: one_chip.traffic[k] for k in same
    }
    assert [m["name"] for m in cell.end_to_end] == [
        "settled_tx_per_s", "setup_s"
    ]
    mine = {"mesh_shard_gather_pct", "mesh_item_pct"}
    assert {m["name"] for m in cell.per_layer} == mine | {
        m["name"] for m in one_chip.per_layer
    }
    readers = {name: spec.load_reader(name) for name in mine}

    # toy waves sit under every floor, mesh or not: pin the kernels,
    # and bring the comb's floor down to an N=8 share wave
    monkeypatch.setattr(ModEngine, "host_delegation", False)
    monkeypatch.setattr(ModEngine, "HOST_FLOOR", 6)
    monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_VERIFY", 0)
    monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_BUILD_LEAVES", 0)
    monkeypatch.setattr(XlaErasureCoder, "HOST_FLOOR_BYTES", 0)
    add_config(toy_root, "toy-n8-mesh4", "n128-b10k-mesh4",
               {"n": 8, "batch_size": 64})
    add_cell(toy_root, "toy-n8-mesh4.saturated",
             "n128-b10k-mesh4.saturated", "toy-n8-mesh4", chips=4)
    seen = {}

    def keep(name):
        def read(run):
            seen["run"] = run
            return readers[name](run)
        return read

    load = spec.load_reader
    monkeypatch.setattr(
        spec, "load_reader",
        lambda name, root: keep(name) if name in mine else load(name, root),
    )
    result = harness.run_cell(
        "toy-n8-mesh4.saturated", 2**31 + 29, 1.5, True, root=toy_root
    )
    assert result["correct"] is True, result["compared"]
    assert result["device"]["count"] >= 4
    metrics = result["metrics"]
    assert metrics["mesh_item_pct"]["value"] == 100.0
    assert 0.0 < metrics["mesh_shard_gather_pct"]["value"] < 100.0
    assert metrics["modexp_device_item_pct"]["value"] == 100.0
    placement = seen["run"]["counters"]["after"]["placement"]
    assert placement["modexp_12x22.comb"]["mesh_items"] > 0

    # the parent's program: no mesh columns in the tally, no such spans
    run = dict(seen["run"])
    run["counters"] = {
        end: dict(row, placement={
            fam: {k: v for k, v in r.items() if not k.startswith("mesh_")}
            for fam, r in row["placement"].items()
        })
        for end, row in seen["run"]["counters"].items() if "placement" in row
    }
    assert readers["mesh_item_pct"](run) is None
    from cleisthenes_tpu.utils import trace

    table = trace.totals()
    monkeypatch.setattr(trace, "totals", lambda: {
        k: v for k, v in table.items()
        if k not in ("ops/shard", "ops/gather")
    })
    assert readers["mesh_shard_gather_pct"](run) is None
    assert readers["mesh_shard_gather_pct"](dict(run, trace=None)) is None
