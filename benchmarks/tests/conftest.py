"""benchmarks/tests run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of tier-1.  Four virtual CPU devices, so that a
``mesh_shape=(2, 2)`` configuration can run here; both variables have
to be set before the first ``import jax``."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)
ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
