"""The MODP-14 configuration, ``n16-b4k-modp14``, and its cell: a toy
roster in the 2048-bit group runs through ``run_cell`` on the served
path, its exponentiations on the OpenSSL host kernel, and reports its
metrics; the wide family's span reader reads it.  (A new file beside
test_harness.py, whose fixtures and helpers it borrows.)"""

import pytest

from benchmarks.tests.test_harness import (  # noqa: F401
    add_cell,
    add_config,
    harness,
    toy_root,
)

CELL = "toy-modp14.saturated"


@pytest.fixture
def modp14_root(toy_root):
    """The toy checkout with a four-validator copy of the configuration
    and its cell, 64 transactions a batch."""
    add_config(toy_root, "toy-modp14", "n16-b4k-modp14",
               {"n": 4, "batch_size": 64})
    add_cell(toy_root, CELL, "n16-b4k-modp14.saturated", "toy-modp14")
    return toy_root


@pytest.mark.parametrize("trace", [False, True])
def test_a_toy_roster_in_modp14_runs_on_the_served_path(
    harness, modp14_root, trace
):
    from cleisthenes_tpu.ops import widepow

    widepow.reset_modpow_wide_tally()
    result = harness.run_cell(CELL, 2**31 + 43, 2.0, trace, root=modp14_root)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert widepow.modpow_wide_tally()["path"] == "openssl"
    metrics = result["metrics"]
    if not trace:
        assert sorted(metrics) == ["settled_tx_per_s", "setup_s"]
        return
    assert metrics["modexp_wide_ms_per_epoch"]["value"] > 0


def test_the_same_roster_in_the_default_group_leaves_the_wide_metric_out(
    harness, toy_root
):
    """The control: ``correct`` compares ledgers and sees no group, so a
    roster dealt in the 256-bit group reads correct too; what the cell's
    traced run holds it to is a value for ``modexp_wide_ms_per_epoch``,
    which a program that ran no 2048-bit batch does not report."""
    from cleisthenes_tpu.utils import trace

    # the span table is the process's; a cell runs alone in a process
    trace.reset_totals()
    add_config(toy_root, "toy-modp14-off", "n16-b4k-modp14",
               {"n": 4, "batch_size": 64, "crypto_group": "default"})
    add_cell(toy_root, "toy-modp14-off.saturated",
             "n16-b4k-modp14.saturated", "toy-modp14-off")
    result = harness.run_cell("toy-modp14-off.saturated", 2**31 + 43, 2.0,
                              True, root=toy_root)
    assert result["correct"] is True, result["compared"]
    assert "modexp_wide_ms_per_epoch" not in result["metrics"]
