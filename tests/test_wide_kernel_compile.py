"""The 2048-bit ladders as the chip's compiler sees them: the (11, 192)
limb family's pow and dual-pow programs, with their ladders as Pallas
kernels (``modmath._on_tpu`` steered here, in the test), compiled for a
described v5e chip at a 1,024-row tile.  Nothing runs; the compiler
refuses what the interpreter and the CPU accept (an operation a kernel
cannot lower, more vector memory than the kernel may hold).  The other
wide families' ladders stay plain XLA on a TPU."""

import pytest

from cleisthenes_tpu.ops import modmath as mm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels(monkeypatch):
    import jax

    monkeypatch.setattr(mm, "_on_tpu", lambda: True)
    jax.clear_caches()
    yield mm._make_programs(mm.LimbLayout(11, 192))
    jax.clear_caches()


@pytest.mark.parametrize("op", ["pow", "dual_pow"])
def test_the_wide_ladder_compiles_for_the_chip(one_chip, kernels, op):
    import jax
    import jax.numpy as jnp

    lay = mm.LimbLayout(11, 192)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = arg((1024, lay.val_bytes), jnp.uint8)
    group = (
        arg((lay.nlimbs,), jnp.int32), arg((), jnp.int32),
        arg((lay.nlimbs,), jnp.int32), arg((lay.nlimbs,), jnp.int32),
    )
    program = kernels[0] if op == "pow" else kernels[1]
    args = (rows,) * (2 if op == "pow" else 4)
    compiled = program.lower(*args, *group).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("lay", [mm.LimbLayout(12, 32), mm.LimbLayout(11, 72)])
def test_the_other_wide_families_stay_plain_xla(monkeypatch, lay):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(mm, "_on_tpu", lambda: True)
    jax.clear_caches()
    try:
        pow_prog, dual_prog = mm._make_programs(lay)
        rows = jax.ShapeDtypeStruct((8, lay.val_bytes), jnp.uint8)
        group = (
            jax.ShapeDtypeStruct((lay.nlimbs,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((lay.nlimbs,), jnp.int32),
            jax.ShapeDtypeStruct((lay.nlimbs,), jnp.int32),
        )
        texts = (
            pow_prog.lower(rows, rows, *group).as_text(),
            dual_prog.lower(rows, rows, rows, rows, *group).as_text(),
        )
    finally:
        jax.clear_caches()
    assert lay not in mm._KERNEL_LAYOUTS
    for text in texts:
        assert "tpu_custom_call" not in text and "dynamic_slice" in text
