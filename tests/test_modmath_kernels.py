"""The Pallas kernels of the 256-bit exponentiation programs, run here
by the Pallas interpreter: on the CPU the programs trace to the plain
XLA form of the same functions (``modmath._on_tpu``), so the kernels'
own scaffolding (block specs, padding to whole tiles, the stores of a
table build, the comb's table reads) is covered by forcing the TPU
branch and interpreting it.  Tiny batches: the interpreter is
slow."""

import functools
import random

import numpy as np
import pytest

from cleisthenes_tpu.ops import modmath as mm


@pytest.fixture
def interpreted_kernels(monkeypatch):
    import jax
    from jax.experimental import pallas as pl

    if mm._native_modpow() is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True)
    )
    monkeypatch.setattr(mm, "_on_tpu", lambda: True)
    monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
    # programs traced under the patches must not outlive them
    jax.clear_caches()
    yield mm.ModEngine("tpu"), mm.ModEngine("cpu")
    jax.clear_caches()


def _columns(rnd, n):
    p = mm.P
    vals = [0, 1, p - 1] + [rnd.randrange(p) for _ in range(n - 3)]
    exps = np.frombuffer(rnd.randbytes(n * 32), dtype=np.uint8).reshape(n, 32)
    exps = exps.copy()
    exps[0], exps[1] = 255, 0
    return mm.ints_to_bytes33(vals), exps


def test_pow_kernel_matches_the_host_kernel(interpreted_kernels):
    dev, host = interpreted_kernels
    u, e = _columns(random.Random(1), 8)
    assert (dev.pow_cols(u, e) == host.pow_cols(u, e)).all()


def test_dual_pow_kernel_matches_the_host_kernel(interpreted_kernels):
    dev, host = interpreted_kernels
    u1, e1 = _columns(random.Random(2), 8)
    u2, e2 = _columns(random.Random(3), 8)
    assert (
        dev.dual_pow_cols(u1, e1, u2, e2) == host.dual_pow_cols(u1, e1, u2, e2)
    ).all()


@pytest.mark.parametrize("rows,width", [(3, 8), (9, 24)])
def test_comb_kernels_match_the_host_kernel(interpreted_kernels, rows, width):
    """To-Montgomery, the squarings, the table rows and the 64 comb
    steps as kernels: a block padded from 3 bases to 8 with 16 items
    of a base side by side, and one of 16 bases with a padded width."""
    dev, host = interpreted_kernels
    rnd = random.Random(rows)
    bases = [1, mm.P - 1] + [rnd.randrange(2, mm.P) for _ in range(rows - 2)]
    exps = np.frombuffer(
        rnd.randbytes(rows * width * 32), dtype=np.uint8
    ).reshape(rows, width, 32).copy()
    exps[0, 0], exps[-1, -1] = 0, 255
    got = dev._comb_device([(bases, exps)])[0]
    assert (got == host.pow_grouped_cols([(bases, exps)])[0]).all()
