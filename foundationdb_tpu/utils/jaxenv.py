"""Platform choice, compile cache, and the host<->device transfer choke points.

The accelerator is local to the process that serves with it: the first
backend-initializing JAX call attaches it in-process, once. There is no probe
and no way onto the CPU unasked:

- JAX_PLATFORMS=cpu set from outside is the operator asking for the CPU, and
  they get it. Tier-1 tests and the simulator run this way, and
  CONFLICT_CPU_FALLBACK (which evaluator a device backend serves with on the
  CPU) has a meaning there only.
- Otherwise the backend JAX finds must be an accelerator. serving_platform()
  raises when it is not, or when attaching raises; the resolver lets that
  propagate, so a server whose chip did not attach dies at boot with the
  reason on stderr instead of serving with another engine.
"""

from __future__ import annotations

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cpu_requested() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def serving_platform() -> str:
    """The platform JAX reports for this process (attaching it if this is
    the first backend call). Raises unless it is an accelerator or the
    operator asked for the CPU."""
    import jax
    platform = jax.default_backend()
    if platform == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator attached: JAX found only the CPU and "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} did not ask "
            "for it (set JAX_PLATFORMS=cpu to serve on the CPU on purpose)")
    return platform


def device_identity() -> dict:
    """What holds this process's programs, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"Platform": devs[0].platform, "DeviceKind": devs[0].device_kind,
            "DeviceCount": len(devs)}


def enable_compile_cache() -> str:
    """Persistent compile cache, called before the first compile by every
    entry point. Where JAX_COMPILATION_CACHE_DIR is set JAX reads it and no
    path is set in code; otherwise the cache lives in the checkout, and the
    variable is exported so child processes share it."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[CACHE_ENV] = path
    if "jax" in sys.modules:  # already imported: the variable was read then
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Sanctioned transfer choke points (devlint DEV007).
#
# All host<->device transfers route through here so every transfer is
# counted: a raw jax.device_put sprinkled elsewhere moves bytes the
# resolver's metrics never see.
# ---------------------------------------------------------------------------

from foundationdb_tpu.utils.stats import CounterCollection

# Process-wide transfer gauges, fed by the choke points below and merged
# into the resolver's RESOLVER_METRICS snapshot. Explicit transfers count
# themselves (device_put/device_get); the served path's two implicit ones
# (the batch into the jit call, the status array out through np.asarray)
# are counted where they happen, through count_device_put/count_device_get.
transfer_metrics = CounterCollection("JaxTransfers")
_put_count = transfer_metrics.counter("DevicePuts")
_put_bytes = transfer_metrics.counter("DevicePutBytes")
_get_count = transfer_metrics.counter("DeviceGets")
_get_bytes = transfer_metrics.counter("DeviceGetBytes")


def _nbytes(x) -> int:
    try:
        import jax
        return sum(int(getattr(leaf, "nbytes", 0) or 0)
                   for leaf in jax.tree_util.tree_leaves(x))
    except Exception:  # noqa: BLE001 — accounting must never fail a transfer
        return 0


def count_device_put(x) -> None:
    """Count host arrays that cross to the device inside a jit call (the
    served path hands the encoded batch to the step program: the transfer is
    implicit and never passes device_put)."""
    _put_count.increment()
    _put_bytes.increment(_nbytes(x))


def count_device_get(x) -> None:
    """Count device arrays materialised on the host by np.asarray (the
    served path's status readback never passes device_get)."""
    _get_count.increment()
    _get_bytes.increment(_nbytes(x))


def device_put(x, sharding=None):
    """jax.device_put through the counting choke point."""
    import jax
    count_device_put(x)
    return jax.device_put(x, sharding) if sharding is not None \
        else jax.device_put(x)


def device_get(x):
    """jax.device_get through the counting choke point."""
    import jax
    count_device_get(x)
    return jax.device_get(x)
