"""PR 34's configuration, ``n16-b16k-tx250``, and its cell: the files
load by name and differ from ``n16-b4k`` in the paper's two widths
alone, a toy roster at 250 bytes runs through ``run_cell`` with its RS
waves on the device path and reports the byte metrics, and the kernels'
check (benchmarks/erasure_kernels.py) holds at a small length.  (A new
file beside test_harness.py, whose fixtures and helpers it borrows.)"""

import pytest

from benchmarks.tests.test_harness import (  # noqa: F401
    add_cell,
    add_config,
    harness,
    toy_root,
)

CELL = "toy-tx250.saturated"
NEW_METRICS = {"decode_recheck_roofline", "rs256_roofline",
               "erasure_device_byte_pct", "wire_mb_per_epoch"}


@pytest.fixture
def width_root(toy_root):
    """The toy checkout with a four-validator copy of the paper-width
    configuration and its cell.  1,280 transactions a batch: a proposer's
    320 transactions of 250 bytes are an (2, 40,704) matrix, 81 KB, over
    the coder's 64 KiB floor by their width."""
    add_config(toy_root, "toy-tx250", "n16-b16k-tx250",
               {"n": 4, "batch_size": 1280})
    add_cell(toy_root, CELL, "n16-b16k-tx250.saturated", "toy-tx250")
    return toy_root


def test_the_configuration_is_n16_b4k_at_the_papers_widths():
    from benchmarks import spec

    cell = spec.load_cell("n16-b16k-tx250.saturated")
    twin = spec.load_cell("n16-b4k.saturated")
    assert cell.chips == 1
    assert cell.config["config"] == dict(
        twin.config["config"], batch_size=16384
    )
    assert cell.config["cluster"] == twin.config["cluster"]
    assert (cell.config["tx_bytes"], twin.config["tx_bytes"]) == (250, 64)
    assert cell.config["guarantees"] == twin.config["guarantees"]
    assert cell.config["reduced"] == twin.config["reduced"]
    assert list(cell.config["reduced"]) == ["wal_dir"]
    assert len(cell.config["source"]) <= 200
    shape = [k for k in twin.traffic if k not in ("who", "why")]
    assert {k: cell.traffic[k] for k in shape} == {
        k: twin.traffic[k] for k in shape
    }
    assert [m["name"] for m in cell.end_to_end] == [
        "settled_tx_per_s", "setup_s"
    ]
    assert {m["name"] for m in cell.per_layer} == NEW_METRICS | {
        m["name"] for m in twin.per_layer
    }
    for name in NEW_METRICS:
        spec.load_reader(name)


@pytest.mark.parametrize("trace", [False, True])
def test_a_toy_roster_at_250_bytes_runs_with_its_rs_waves_on_the_device_path(
    harness, width_root, trace
):
    result = harness.run_cell(CELL, 2**31 + 7, 2.0, trace, root=width_root)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(row == {"value": 0, "limit": 0}
               for row in result["compared"].values())
    metrics = result["metrics"]
    if not trace:
        assert sorted(metrics) == ["settled_tx_per_s", "setup_s"]
        return
    # every RS call crossed its byte floor; the Merkle batches, a few
    # proofs and leaves each, stayed under their item floors
    assert metrics["rs_device_item_pct"]["value"] == 100.0
    assert metrics["merkle_device_item_pct"]["value"] == 0.0
    assert 0 < metrics["erasure_device_byte_pct"]["value"] < 100
    # an epoch's distinct VAL and ECHO bodies: 2 * 4 * 4 shards of 40 KB
    # and their branches, and the small BBA traffic beside them
    assert 1.2 < metrics["wire_mb_per_epoch"]["value"] < 2.0
    # no device plane on the CPU: the roofline readers find no program
    # seconds and the metrics are left out, never reported as 0
    assert "decode_recheck_roofline" not in metrics
    assert "rs256_roofline" not in metrics


def test_the_kernels_check_holds_at_a_small_length():
    from benchmarks import erasure_kernels

    out = erasure_kernels.check_kernels(11008, 5, batches=(8,), repeats=1)
    assert out["equal"] is True
    assert [c["case"] for c in out["cases"]] == [
        "encode", "decode_recheck.worst.dispatches1",
        "decode_recheck.mixed.dispatches3", "verify_batch",
    ]
