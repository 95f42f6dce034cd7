"""The commit path's device programs, compiled for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is described,
not attached (no chip time, nothing runs): it raises what the chip's compiler
would raise — a program too large, a sort it refuses, an unaligned slice.
Code that asks `jax.default_backend()` sees the CPU here, so the donated
programs are jitted by the tests themselves. A compile that passes is not a
chip run; `chip_smoke.py` is.

All of these live in ONE file and describe the topology inside a module
fixture: only one process may load the TPU library, and it is the worker that
is given this file, once one of its tests has started.
"""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from foundationdb_tpu.ops import conflict as C
from foundationdb_tpu.ops.conflict import BatchEncoder, ConflictShapes
from foundationdb_tpu.parallel import sharded_conflict as S

WINDOW = 5_000_000
SMALL = ConflictShapes(capacity=8192, txns=64, reads=128, writes=128)
# the same batch on a state as large as fdb-bench-1chip-1m's
# (benchmark/configs: CONFLICT_STATE_CAPACITY 2^19)
SMALL_BATCH = pytest.mark.parametrize(
    "shapes", [SMALL, dataclasses.replace(SMALL, capacity=1 << 19)],
    ids=["k8192", "k524288"])
# the full bucket chip_smoke.py's core serves with (SERVED_KNOBS)
SERVED = ConflictShapes(capacity=1 << 18, txns=256, reads=2560, writes=2560)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of these
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _specs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _step_args(shapes, sharding):
    return (_specs(C.init_state(shapes), sharding),
            _specs(BatchEncoder(shapes).encode_batch([], 1, shapes=shapes),
                   sharding))


@functools.lru_cache(maxsize=None)
def _donated_step(shapes, device):
    step = jax.jit(functools.partial(
        C.conflict_step, shapes=shapes, max_write_life=WINDOW,
        intra_rounds=0), donate_argnums=(0,))
    return step.lower(
        *_step_args(shapes, SingleDeviceSharding(device))).compile()


def _compile_donated_step(shapes, topo):
    compiled = _donated_step(shapes, topo.devices[0])
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes > 0
    # the donated state is written in place: the chip holds one copy of it
    assert mem.alias_size_in_bytes > 0
    return compiled


@SMALL_BATCH
def test_conflict_step_donated_small_shape(topo, shapes):
    _compile_donated_step(shapes, topo)


@SMALL_BATCH
def test_small_step_sorts_the_batch_not_the_state(topo, shapes):
    """The step's order is built from the batch's M rows ranked in the
    sorted state: the compiled module sorts M rows (the eight passes of
    _lex_sort_perm) and nothing of width K + M — the wide sort is gone from
    the program, not bypassed."""
    M = 2 * shapes.reads + 2 * shapes.writes
    widths = set()
    compiled = _compile_donated_step(shapes, topo)
    for line in compiled.as_text().splitlines():
        result, is_sort, _operands = line.partition(") sort(")
        if is_sort:
            widths.update(int(n) for n in re.findall(r"\[(\d+)\]", result))
    assert M in widths, widths
    assert shapes.capacity + M not in widths, widths


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$")


def _wide_moves(hlo_text, widths):
    """The module's irregular moves whose data is as wide as the state:
    gathers whose result, and scatters whose updates, have a dimension in
    `widths`. (An M-row scatter into a K + M wide array, or an M-row gather
    from a K-row table, is not one: what it moves is M rows.)"""
    dims, moves = {}, []
    for line in hlo_text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        name, _dtype, shape, op, rest = m.groups()
        dims[name] = {int(n) for n in shape.split(",") if n}
        if op in ("gather", "scatter"):
            moves.append((name, op, re.findall(r"%([\w.\-]+)",
                                               rest.partition(")")[0])))
    wide = []
    for name, op, operands in moves:
        # scatter(operand.., indices, updates..): the last is an update
        moved = dims[name] if op == "gather" else dims[operands[-1]]
        if moved & widths:
            wide.append(name)
    return wide


@SMALL_BATCH
def test_small_step_never_holds_the_keys_in_merged_order(topo, shapes):
    """The step finds its key groups from the batch's M sorted rows and the
    order's own arithmetic (_group_starts, _batch_key_ranks), and the
    compaction gathers the kept keys from the state itself: no (L, K + M)
    key array exists in the compiled module, and at most four moves are as
    wide as the state. Up to PR 30 there were five, for three arrays: the
    keys gathered into the merged order (`skeys`, (L, K + M)), the values
    gathered into it (`sval`), the compaction's scatter of kept positions,
    and its gathers of keys (from `skeys`) and of values through them. Now:
    `sval`, the compaction's scatters of kept element indices and of kept
    values, and one gather of kept keys from the state."""
    K, L = shapes.capacity, shapes.limbs
    N = K + 2 * shapes.reads + 2 * shapes.writes
    text = _compile_donated_step(shapes, topo).as_text()
    assert not re.search(rf"= u32\[({L},{N}|{N},{L})\]", text)
    assert re.search(rf"= u32\[{L},{K}\]", text)  # the pattern does match
    wide = _wide_moves(text, {K, N})
    assert 1 <= len(wide) <= 4, wide


@pytest.mark.slow(reason="45-80 s to compile (PERF.md, compile times)")
def test_conflict_step_donated_served_shape(topo):
    mem = _compile_donated_step(SERVED, topo).memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30  # 294 MB when written


def test_combine_fn(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    T = SERVED.txns
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    C._combine_fn().lower(sds((T,), jnp.int32), sds((T,), jnp.bool_),
                          sds((), jnp.bool_), sds((), jnp.bool_),
                          sds((), jnp.int32), sds((), jnp.int32)).compile()


def test_rebase_donated(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    rebase = jax.jit(C.rebase_state, donate_argnums=(0,))
    compiled = rebase.lower(
        _specs(C.init_state(SMALL), one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_sharded_step_on_four_device_mesh(topo, monkeypatch):
    """The SPMD step over a mesh of the four described chips, state sharded
    along the resolver axis, batch replicated: the verdict combine must
    compile to cross-chip collectives."""
    # the chip donates the state; default_backend() here says cpu
    monkeypatch.setattr(C, "_donate_state_argnums", lambda: (0,))
    mesh = Mesh(np.asarray(topo.devices[:4]), (S.RESOLVER_AXIS,))
    shapes = ConflictShapes(capacity=1024, txns=16, reads=32, writes=32)
    step = S._build_sharded_step(mesh, shapes, WINDOW, shapes.txns // 2 + 1)
    state = _specs(S.init_sharded_state(shapes, 4),
                   NamedSharding(mesh, P(S.RESOLVER_AXIS)))
    batch = _specs(BatchEncoder(shapes).encode_batch([], 1, shapes=shapes),
                   NamedSharding(mesh, P()))
    compiled = step.lower(state, batch).compile()
    assert "all-reduce" in compiled.as_text()  # pmin/pmax over the mesh
    # each chip holds a quarter of the stacked state, not all of it
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(np.prod(s.shape) * s.dtype.itemsize
                for s in jax.tree.leaves(state))
    assert per_device < whole / 2
