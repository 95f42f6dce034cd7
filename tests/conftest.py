"""Test configuration.

Tests run on a virtual 8-device CPU mesh (multi-chip hardware is not available
in CI): JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8 must be set
before jax is imported anywhere, hence the env mutation at module import time.
chip_smoke.py, benchmark/run.py and __graft_entry__.py do NOT import this —
they run on real TPU.
"""

import os
import time

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


def pytest_collection_modifyitems(items):
    """test_bench_smoke.py goes last. Its slices are closed loops over real
    processes on this host, and since the commit batcher stopped waiting
    out a timer they run at the speed the host gives them: beside five
    busy test workers the five processes of the fan-out topology lose more
    of it than the three of the merged one, and test_scale_out_not_collapsed
    reads the host. Handed out last (xdist's loadfile gives files out in
    collection order) the module runs beside the suite's stragglers only,
    and _wait_for_a_quiet_host lets those finish. The sort is stable, so
    every other test keeps its place."""
    items.sort(key=lambda item: item.path.name == "test_bench_smoke.py")


def _idle_share(seconds: float) -> float:
    """The share of the host's CPU time left idle over the next `seconds`
    (/proc/stat's first line: user nice system idle iowait ...)."""
    def read():
        with open("/proc/stat") as f:
            ticks = [int(x) for x in f.readline().split()[1:]]
        return ticks[3] + ticks[4], sum(ticks)
    idle0, all0 = read()
    time.sleep(seconds)
    idle1, all1 = read()
    return (idle1 - idle0) / max(1, all1 - all0)


_bench_smoke_waited = False


def pytest_runtest_setup(item):
    """Before test_bench_smoke.py's first test: wait, three minutes at most,
    until the host has been two thirds idle for a second. The module's
    clusters are measured, not just exercised, and a measurement is made
    when the host can carry it; nothing is measured twice."""
    global _bench_smoke_waited
    if _bench_smoke_waited or item.path.name != "test_bench_smoke.py":
        return
    _bench_smoke_waited = True
    deadline = time.monotonic() + 180.0
    try:
        while time.monotonic() < deadline and _idle_share(1.0) < 0.67:
            pass
    except OSError:  # no /proc/stat on this host: measure as it is
        pass
