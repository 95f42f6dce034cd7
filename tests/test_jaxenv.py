"""utils/jaxenv: no way onto the CPU unasked, and where the compile cache goes.

CPU only. The process under test reports `cpu` from jax.default_backend();
what changes between cases is whether the operator ASKED for it
(JAX_PLATFORMS=cpu from outside), steered here with monkeypatch.
"""

import os

import pytest

import jax

from foundationdb_tpu.ops.conflict import DeviceConflictSet
from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
from foundationdb_tpu.server.resolver import new_conflict_set
from foundationdb_tpu.utils import jaxenv
from foundationdb_tpu.utils.knobs import KNOBS


@pytest.mark.parametrize("backend", ["device", "sharded"])
@pytest.mark.parametrize("platforms", [None, "", "tpu", "tpu,cpu"])
def test_device_backend_without_accelerator_raises(monkeypatch, backend,
                                                   platforms):
    """JAX found only the CPU and nobody asked for it: the resolver must not
    come up on the oracle (or on XLA:CPU) under the device label."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert jax.default_backend() == "cpu"
    KNOBS.set("CONFLICT_BACKEND", backend)
    with pytest.raises(RuntimeError, match="no accelerator attached"):
        new_conflict_set()


@pytest.mark.parametrize("fallback,engine,label", [
    ("host", OracleConflictSet, "cpu+host-evaluator"),
    ("jax", DeviceConflictSet, "cpu"),
])
def test_cpu_asked_for_serves_as_before(monkeypatch, fallback, engine, label):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    KNOBS.set("CONFLICT_BACKEND", "device")
    KNOBS.set("CONFLICT_CPU_FALLBACK", fallback)
    for k, v in (("CONFLICT_STATE_CAPACITY", 256), ("CONFLICT_BATCH_TXNS", 8),
                 ("CONFLICT_BATCH_READS_PER_TXN", 2),
                 ("CONFLICT_BATCH_WRITES_PER_TXN", 2)):
        KNOBS.set(k, v)
    cs = new_conflict_set()
    assert type(cs) is engine
    assert cs.backend_label == label


def test_oracle_backend_never_asks_for_a_device(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jaxenv, "serving_platform",
                        lambda: pytest.fail("oracle backend touched JAX"))
    KNOBS.set("CONFLICT_BACKEND", "oracle")
    assert type(new_conflict_set()) is OracleConflictSet


def test_device_identity_is_what_jax_reports():
    devs = jax.devices()
    assert jaxenv.device_identity() == {
        "Platform": devs[0].platform, "DeviceKind": devs[0].device_kind,
        "DeviceCount": len(devs)}


def test_cache_helper_leaves_a_set_variable_alone(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert jaxenv.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # no path in code


def test_cache_helper_defaults_inside_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        path = jaxenv.enable_compile_cache()
        assert path == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # exported, so server children share the parent's cache
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
