"""Test configuration.

Tests run on a virtual 8-device CPU mesh (multi-chip hardware is not available
in CI): JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8 must be set
before jax is imported anywhere, hence the env mutation at module import time.
bench.py and __graft_entry__.py do NOT import this — they run on real TPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402
import jax  # noqa: E402

from foundationdb_tpu.utils.jaxenv import enable_compile_cache  # noqa: E402
from foundationdb_tpu.utils.knobs import KNOBS  # noqa: E402

# Persistent compile cache: the conflict-engine program is compiled once per
# (shapes, window) and reused across test runs.
enable_compile_cache()


@pytest.fixture(autouse=True)
def _reset_knobs():
    KNOBS.reset()
    yield
    KNOBS.reset()
