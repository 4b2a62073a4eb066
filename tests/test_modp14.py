"""RFC 3526's 2048-bit MODP group 14 as a deployment's group
(``Config.crypto_group = "modp14"``): the group's constants, the host
kernel that runs its exponentiations (native/modpow_wide.cpp through
``ops.widepow``) and the (11, 192) limb family's programs, each against
the plain reference (benchmarks/reference_modexp.py, Python's pow()
over the RFC's prime); the configuration field and the dealer; and a
served four-validator roster in the group."""

from __future__ import annotations

import pathlib
import random
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import reference_modexp as ref  # noqa: E402
from cleisthenes_tpu.config import Config  # noqa: E402
from cleisthenes_tpu.ops import modmath as mm  # noqa: E402
from cleisthenes_tpu.ops import placement, widepow  # noqa: E402

ROWS = 12


def _columns(seed: int, rows: int = ROWS):
    """Seeded bases and full-width exponents mod q, with the edge rows:
    bases 0, 1 and p - 1, exponents 0 and q - 1, a zero base with a
    zero exponent in a dual row."""
    rnd = random.Random(seed)
    u1 = [0, 1, ref.P - 1] + [rnd.randrange(ref.P) for _ in range(rows - 3)]
    e1 = [0, ref.Q - 1] + [rnd.randrange(ref.Q) for _ in range(rows - 2)]
    u2 = [rnd.randrange(ref.P) for _ in range(rows)]
    e2 = [rnd.randrange(ref.Q) for _ in range(rows)]
    u2[3], e2[3] = 0, 0
    return u1, e1, u2, e2


# -- the group -----------------------------------------------------------


def test_the_packaged_group_is_rfc_3526s_group_14():
    ref.check_group()
    assert (mm.GROUP14.p, mm.GROUP14.q, mm.GROUP14.g) == (ref.P, ref.Q, ref.G)
    assert mm.MODP14 == ref.P and mm.GROUP14.nbytes == ref.NBYTES
    assert mm.layout_for_group(mm.GROUP14) == mm.LimbLayout(11, 192)
    assert tuple(mm.GROUPS) == ("default", "modp14")
    assert mm.group_named("default") is mm.DEFAULT_GROUP
    assert mm.group_named("modp14") is mm.GROUP14
    with pytest.raises(ValueError, match="unknown crypto_group"):
        mm.group_named("modp15")


# -- the host kernel -------------------------------------------------------


@pytest.fixture
def native():
    if widepow.load_modpow_wide() is None:
        pytest.skip("no native toolchain or no libcrypto")
    widepow.reset_modpow_wide_tally()


@pytest.mark.parametrize("threads", [1, 3, 0])
def test_the_host_kernel_is_the_references(native, threads):
    u1, e1, u2, e2 = _columns(threads)
    assert widepow.pow_rows(u1, e1, ref.P, threads) == ref.pow_rows(u1, e1)
    assert widepow.dual_pow_rows(
        u1, e1, u2, e2, ref.P, threads
    ) == ref.dual_pow_rows(u1, e1, u2, e2)
    tally = widepow.modpow_wide_tally()
    assert tally["path"] == "openssl"
    assert (tally["calls"], tally["rows"]) == (2, 2 * ROWS)
    if threads == 3:
        assert tally["threaded_calls"] == 2 and tally["threads"] == 3


def test_the_host_kernel_takes_bases_past_the_modulus_and_empty_batches(
    native,
):
    # bases reduce first, as pow() does; an empty batch is no call
    assert widepow.pow_rows([ref.P + 5, 2 * ref.P], [3, 7], ref.P) == [
        125, 0
    ]
    assert widepow.pow_rows([], [], ref.P) == []
    assert widepow.dual_pow_rows([], [], [], [], ref.P) == []
    assert widepow.modpow_wide_tally()["calls"] == 1
    # the engine of the group on the host side is the same kernel
    u1, e1, u2, e2 = _columns(7)
    host = mm.ModEngine("cpu", group=mm.GROUP14)
    assert host.pow_batch(u1, e1) == ref.pow_rows(u1, e1)
    assert host.dual_pow_batch(u1, e1, u2, e2) == ref.dual_pow_rows(
        u1, e1, u2, e2
    )
    assert mm.host_pow_batch(u1, e1, mm.GROUP14) == ref.pow_rows(u1, e1)


def test_without_the_library_the_fallback_is_pow(monkeypatch):
    monkeypatch.setattr(widepow, "load_modpow_wide", lambda: None)
    widepow.reset_modpow_wide_tally()
    u1, e1, u2, e2 = _columns(11, rows=5)
    assert widepow.pow_rows(u1, e1, ref.P) == ref.pow_rows(u1, e1)
    assert widepow.dual_pow_rows(u1, e1, u2, e2, ref.P) == (
        ref.dual_pow_rows(u1, e1, u2, e2)
    )
    assert widepow.modpow_wide_tally()["path"] == "python"


# -- the device programs, as plain XLA here --------------------------------


def test_the_wide_programs_are_the_references_under_their_names(
    jax_cpu_devices, monkeypatch
):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(mm.ModEngine, "host_delegation", False)
    eng = mm.ModEngine("tpu", group=mm.GROUP14)
    u1, e1, u2, e2 = _columns(5, rows=8)
    placement.reset()
    assert eng.pow_batch(u1, e1) == ref.pow_rows(u1, e1)
    assert eng.dual_pow_batch(u1, e1, u2, e2) == ref.dual_pow_rows(
        u1, e1, u2, e2
    )
    tally = placement.snapshot()
    assert tally["modexp_11x192.pow"]["device_items"] == 8
    assert tally["modexp_11x192.dual_pow"]["device_items"] == 8
    # the profiler's modules: jit__pow_wide / jit__dual_pow_wide, apart
    # from the 256-bit programs' names, which stay as they were
    lay = mm.LimbLayout(11, 192)
    pow_prog, dual_prog = mm._wide_kernels(lay)
    rows = jax.ShapeDtypeStruct((8, lay.val_bytes), jnp.uint8)
    spec_ = eng._xla_spec
    assert "jit__pow_wide" in pow_prog.lower(rows, rows, *spec_).as_text()
    assert "jit__dual_pow_wide" in dual_prog.lower(
        rows, rows, rows, rows, *spec_
    ).as_text()
    assert (mm._pow_fused.__name__, mm._dual_pow_fused.__name__) == (
        "_pow_fused", "_dual_pow_fused"
    )
    assert mm._pow_fused_grouped.__name__ == "_pow_fused_grouped"


# -- the deployment names its group ---------------------------------------


def test_the_configuration_names_its_group():
    assert Config().crypto_group == "default"
    assert Config(crypto_group="modp14").crypto_group == "modp14"
    with pytest.raises(ValueError, match="crypto_group"):
        Config(crypto_group="modp15")


def test_the_dealer_deals_in_the_configurations_group():
    from cleisthenes_tpu.protocol.honeybadger import setup_keys

    ids = [f"node{i}" for i in range(4)]
    keys = setup_keys(Config(n=4, crypto_group="modp14"), ids, seed=43)
    for nid in ids:
        assert keys[nid].tpke_pub.group is mm.GROUP14
        assert keys[nid].coin_pub.group is mm.GROUP14
    vk = keys[ids[0]].tpke_pub.verification_keys[0]
    assert 1 < vk < ref.P and pow(vk, ref.Q, ref.P) == 1
    # a group passed in still wins; the default config deals 256 bits
    assert setup_keys(
        Config(n=4, crypto_group="modp14"), ids, seed=43,
        group=mm.DEFAULT_GROUP,
    )[ids[0]].tpke_pub.group is mm.DEFAULT_GROUP
    assert setup_keys(Config(n=4), ids, seed=43)[
        ids[0]
    ].tpke_pub.group is mm.DEFAULT_GROUP


def test_a_served_roster_in_the_group_settles_every_transaction_once(
    native,
):
    from cleisthenes_tpu.protocol.cluster import SimulatedCluster

    placement.reset()
    cluster = SimulatedCluster(
        config=Config(n=4, batch_size=32, seed=43, crypto_backend="tpu",
                      crypto_group="modp14"),
        seed=43,
        key_seed=44,
    )
    try:
        assert cluster.nodes[cluster.ids[0]].tpke.group is mm.GROUP14
        txs = [b"modp14-%04d" % i + bytes(53) for i in range(64)]
        for tx in txs:
            cluster.submit(tx)
        cluster.run_epochs()
        depth = cluster.assert_agreement()
        settled = [
            tx for batch in cluster.committed()[:depth]
            for tx in batch.tx_list()
        ]
    finally:
        cluster.stop()
    assert sorted(settled) == sorted(txs)  # each exactly once
    tally = widepow.modpow_wide_tally()
    assert tally["path"] == "openssl" and tally["rows"] > 0
    wide = {fam: row for fam, row in placement.snapshot().items()
            if fam.startswith("modexp_")}
    assert set(wide) <= {"modexp_11x192.pow", "modexp_11x192.dual_pow"}
    assert wide["modexp_11x192.pow"]["host_items"] > 0
