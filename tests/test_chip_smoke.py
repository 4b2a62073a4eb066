"""chip_smoke.py debugged here before it spends chip time: its stage
functions at toy size on XLA-CPU, and the platform gate."""

import os
import subprocess
import sys

import pytest

import chip_smoke
from cleisthenes_tpu.ops.merkle import XlaMerkle
from cleisthenes_tpu.ops.modmath import ModEngine
from cleisthenes_tpu.ops.rs_xla import XlaErasureCoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY_KERNELS = dict(
    pow_rows=16,
    dual_rows=16,
    comb_roster=16,  # 6 n^2 = 1536 exps: just over the comb floor
    wide384_rows=8,
    wide792_rows=8,
    gf256=(8, 2, 128),
    gf256_single_len=128,
    gf65536=(8, 2, 128),
    merkle=((8, 128), (16, 128)),
)


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


@pytest.fixture
def device_arm(monkeypatch):
    """Toy batches sit below every host floor; pin them to the XLA
    kernels so the device arm of the tally is what runs."""
    monkeypatch.setattr(ModEngine, "host_delegation", False)
    monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_VERIFY", 0)
    monkeypatch.setattr(XlaMerkle, "HOST_FLOOR_BUILD_LEAVES", 0)
    monkeypatch.setattr(XlaErasureCoder, "HOST_FLOOR_BYTES", 0)


def test_platform_gate_refuses_cpu():
    """`python chip_smoke.py` off the chip: non-zero exit naming the
    platform, no result line, before any stage runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr


def test_native_stage():
    chip_smoke.stage_native()


def test_served_stage_toy(meter, device_arm):
    report = chip_smoke.stage_served(
        meter, n=4, batch=32, txs=64, ticks=2, seed=7
    )
    assert report["device_items"] > 0
    # the modexp families reached the device; whatever did not is named
    assert "modexp_12x22.dual_pow" not in report["never_on_device"]
    assert report["compiles"] > 0


def test_served_stage_names_idle_device(meter):
    """With the floors in force a toy roster sends nothing to the
    device, and the stage must fail saying so — the smoke's reason to
    exist."""
    with pytest.raises(chip_smoke.SmokeFailure, match="NOTHING"):
        chip_smoke.stage_served(
            meter, n=4, batch=32, txs=64, ticks=2, seed=7
        )


def test_lockstep_stage_toy(meter, device_arm):
    report = chip_smoke.stage_lockstep(
        meter, n=8, batch=64, epochs=2, seed=7
    )
    assert report["device_items"] > 0
    assert len(report["bodies"]) == 3
    assert report["compiles_after_warmup"] is not None


def test_kernels_stage_toy(meter, device_arm):
    report = chip_smoke.stage_kernels(meter, sizes=TOY_KERNELS, seed=7)
    assert report["compiles_after_warmup"] == 0
    assert not report["never_on_device"]


def test_four_chip_stage_on_virtual_devices(meter, device_arm, monkeypatch):
    """The (2, 2) mesh arm against the single-device arm, on four of
    the suite's eight virtual CPU devices (peak memory is a TPU-only
    statistic, so the stage's last check is expected to be the one
    that refuses here: the digests agreed, comb items ran sharded and
    no device batch of the mesh arm ran on one device)."""
    # an N=8 share wave is 64 items: bring the comb's floor down to it
    monkeypatch.setattr(ModEngine, "HOST_FLOOR", 6)
    with pytest.raises(chip_smoke.SmokeFailure, match="never held memory"):
        chip_smoke.stage_four_chip(
            meter, n=8, batch=64, epochs=1, seed=7, reference=None
        )


def test_four_chip_stage_refuses_a_mesh_without_the_comb(
    meter, device_arm
):
    """With the comb's floor above the toy waves every grouped wave
    flattens to the generic kernel, as every mesh wave did before the
    comb ran sharded: the stage says so."""
    with pytest.raises(chip_smoke.SmokeFailure, match="no comb item"):
        chip_smoke.stage_four_chip(
            meter, n=8, batch=64, epochs=1, seed=7, reference=None
        )
