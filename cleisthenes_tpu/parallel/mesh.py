"""The crypto-plane device mesh: in-framework multi-chip sharding.

SURVEY.md §2.2/§5.7 pin the two parallel axes this framework owns:

- ``'v'`` — the validator/instance axis.  N concurrent RBC instances
  (one per proposer, reference docs/HONEYBADGER-EN.md:85-89,
  rbc/rbc.go:17) produce N independent tensor workloads per epoch;
  sharding the batch axis over 'v' is the data-parallel axis.
- ``'l'`` — the shard-length axis.  RS coding is GF(2)-linear along a
  shard's byte columns, so the length axis shards cleanly — the
  framework's sequence-parallel analogue (SURVEY.md §5.7: "shard the
  RS/Merkle/TPKE tensors along the shard-length axis across v5e
  cores").

Placement policy per kernel family:

- RS encode/decode (``ops.rs_xla``): 2-D ``P('v', None, 'l')`` — the
  contraction is over the k-shard axis, so both batch and length shard
  with zero collectives.
- Merkle forest / branch verify / modexp (``ops.sha256_xla``,
  ``ops.modmath``): hashing and exponentiation are sequential *within*
  an element but independent *across* the batch, so the batch axis
  shards over ALL devices flat: ``P(('v','l'))``.

XLA's GSPMD does the partitioning: we place the inputs with
``jax.device_put`` under a ``NamedSharding`` and call the exact same
jitted kernels; resharding between the RS layout and the flat layout
is the compiler-inserted ICI collective (the all-gather the
``__graft_entry__`` dry run demonstrates).

Everything works identically on the 8-virtual-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) that tests
and the driver's ``dryrun_multichip`` use — no TPU needed to exercise
the sharding paths.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from cleisthenes_tpu.utils import trace


def validate_mesh_shape(mesh_shape) -> Tuple[int, int]:
    """Normalize/validate a (v, l) mesh shape (shared by Config and
    CryptoMesh so both layers accept exactly the same shapes).
    Importable without jax."""
    ms = tuple(mesh_shape)
    # bool is an int subclass: (True, True) must not validate as (1, 1)
    if len(ms) != 2 or any(
        isinstance(d, bool) or (not isinstance(d, int)) or d < 1 for d in ms
    ):
        raise ValueError(
            f"mesh_shape must be two positive ints (v, l), got {mesh_shape!r}"
        )
    return ms


class CryptoMesh:
    """A ('v', 'l') jax.sharding.Mesh plus the placement helpers the
    crypto plane uses.

    ``mesh_shape=(v, l)`` is ``Config.mesh_shape``; devices default to
    ``jax.devices()`` (the first v*l of them).
    """

    def __init__(
        self, mesh_shape: Tuple[int, int], devices: Optional[Sequence] = None
    ):
        import jax
        from jax.sharding import Mesh

        v, l = validate_mesh_shape(mesh_shape)
        if devices is None:
            devices = jax.devices()
        if len(devices) < v * l:
            raise ValueError(
                f"mesh {mesh_shape} needs {v * l} devices, "
                f"have {len(devices)}"
            )
        self.shape = (v, l)
        self.n_devices = v * l
        self.mesh = Mesh(
            np.asarray(devices[: v * l]).reshape(v, l), ("v", "l")
        )

    # -- shardings ---------------------------------------------------------

    def _sharding(self, spec):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, spec)

    def spec_vl(self, ndim: int):
        """P('v', None, ..., 'l'): batch over 'v', last axis over 'l'
        (the RS-codec layout)."""
        from jax.sharding import PartitionSpec as P

        return self._sharding(P("v", *([None] * (ndim - 2)), "l"))

    def spec_v(self, ndim: int):
        """P('v', None, ...): batch over 'v' only, replicated over 'l'
        (per-instance matrices whose trailing axes are contractions)."""
        from jax.sharding import PartitionSpec as P

        return self._sharding(P("v", *([None] * (ndim - 1))))

    def spec_flat(self, ndim: int):
        """P(('v','l'), None, ...): batch axis over every device (the
        hash/modexp layout)."""
        from jax.sharding import PartitionSpec as P

        return self._sharding(P(("v", "l"), *([None] * (ndim - 1))))

    # -- placement ---------------------------------------------------------
    #
    # The whole multi-chip seam: host array -> ``put_*`` (span
    # ``ops/shard``) -> the same jitted program, partitioned by GSPMD
    # -> ``gather`` (span ``ops/gather``) -> host array.  No layout
    # the crypto plane uses puts a collective between the two.

    def _span(self, name: str, layout: str):
        return trace.span("ops", name, devices=self.n_devices, layout=layout)

    def put_vl(self, x):
        """Place an array batch-over-'v', length-over-'l'."""
        import jax

        with self._span("shard", "vl"):
            return jax.device_put(x, self.spec_vl(np.ndim(x)))

    def put_v(self, x):
        """Place an array batch-over-'v', everything else replicated."""
        import jax

        with self._span("shard", "v"):
            return jax.device_put(x, self.spec_v(np.ndim(x)))

    def put_flat(self, *arrays):
        """Place arrays with the batch axis sharded over all devices.
        Returns a tuple matching the inputs."""
        import jax

        with self._span("shard", "flat"):
            return tuple(
                jax.device_put(a, self.spec_flat(np.ndim(a)))
                for a in arrays
            )

    def gather(self, x, layout: str) -> np.ndarray:
        """A sharded result as one host array.  The program has run to
        its end before the ``ops/gather`` span opens, so the span is
        the collecting of the shards and not the waiting for them."""
        import jax

        jax.block_until_ready(x)
        with self._span("gather", layout):
            return np.asarray(x)

    # -- batch padding -----------------------------------------------------

    @staticmethod
    def pad_rows(a: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
        """Pad axis 0 up to a multiple by repeating row 0 (valid data,
        so padded lanes execute the same math); returns (padded,
        original_len)."""
        b = a.shape[0]
        pad = (-b) % multiple
        if pad:
            a = np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
        return a, b

    @staticmethod
    def pad_cols(a: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
        """Zero-pad the LAST axis up to a multiple; returns (padded,
        original_len).  Used for the 'l' (shard-length) axis, where
        byte columns are independent under GF coding."""
        l = a.shape[-1]
        pad = (-l) % multiple
        if pad:
            widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
            a = np.pad(a, widths)
        return a, l


def host_array(mesh: Optional[CryptoMesh], x, layout: str = "flat"):
    """A program's result as one host array: ``np.asarray`` on one
    device, ``mesh.gather`` (and its span) under a mesh."""
    if mesh is None:
        return np.asarray(x)
    return mesh.gather(x, layout)


def make_crypto_mesh(
    mesh_shape: Optional[Tuple[int, int]],
    devices: Optional[Sequence] = None,
) -> Optional[CryptoMesh]:
    """None-passthrough constructor (mesh_shape=None = single-device)."""
    if mesh_shape is None:
        return None
    return CryptoMesh(tuple(mesh_shape), devices)


__all__ = [
    "CryptoMesh",
    "host_array",
    "make_crypto_mesh",
    "validate_mesh_shape",
]
