"""Key-partitioned conflict engine over a device mesh (SPMD via shard_map).

TPU-native analogue of the reference's multi-resolver scale-out (SURVEY.md
§2.0): the proxy splits every transaction's conflict ranges across resolvers
by a key-range map (MasterProxyServer.actor.cpp:283-306) and a transaction
commits only if every touched resolver said Committed — the proxy takes the
min over resolver verdicts (:492-504). Here each mesh device IS one resolver
shard:

- The versioned step-function state lives sharded along a `resolvers` mesh
  axis; shard d owns keys in [cut_d, cut_{d+1}). A cut is a whole key (any
  length the key codec takes) and part of the state, not of the program: a
  cold engine starts on equal cuts of its owned range and moves them to
  quantiles of the keys it is offered (resolutionBalancing, below) between
  steps, never inside the jitted step.
- Each device clips the (replicated) batch's ranges to its shard. Clipping to
  an empty range makes the range inert in every phase of conflict_step
  (history check, intra-batch, merge all skip empty ranges), which reproduces
  "this resolver was not touched" without dynamic shapes.
- Per-txn statuses combine with lax.pmin over the axis: status numbering
  (Conflict=0 < TooOld=1 < Committed=2, ConflictSet.h:36-40) makes min exactly
  the proxy's combine rule.

Intra-batch semantics match the reference's per-resolver behavior: each
resolver applies "earlier transactions win" to the ranges it owns and merges
the writes of transactions *it* judged committed — a transaction aborted only
on another shard still leaves its writes in this shard's history. That can
only create false conflicts (safe), never false commits, and is identical to
the reference (Resolver.actor.cpp resolveBatch never learns other resolvers'
verdicts).

All collectives ride the mesh axis (ICI on a real slice); the host feeds one
replicated batch per step — no per-shard host round-trips.

resolutionBalancing (masterserver.actor.cpp:955-1012; a resolver answers a
ResolutionSplitRequest with a full sampled key, Resolver.actor.cpp:279-284).
The engine watches what it is offered, from the limbs the encoder has made
anyway: per shard the ranges that clip non-empty, a reservoir of whole begin
keys that forgets at the rate it is fed, and each shard's boundary count as
the step itself reports it (`info["fill"]`, read one step late, plus twice
the writes dispatched since: an upper bound). It looks at the counts every
RESOLUTION_BALANCE_CHECK_BATCHES steps, and at every step while a shard's
bound is over FILL_SHED of its capacity; it moves the cuts when the busiest
shard is offered more than RESOLUTION_BALANCE_SKEW times the mean and the
sample's quantiles (`plan_cuts`) would give that shard a tenth less. The
resolver role's conflict-mass path plans with the same function. A move is
`rebalance_cuts`: what a shard keeps stays exact, what it acquires is filled
at the move's version — more conflicts for a while, never a false commit.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from foundationdb_tpu.utils import keys as keylib
from foundationdb_tpu.ops.batch import TOO_OLD, TxnConflictInfo
from foundationdb_tpu.ops.conflict import (
    ConflictShapes, L, NEG, _REBASE_THRESHOLD, _key_lt, conflict_step,
    init_state, rebase_state)
from foundationdb_tpu.utils.knobs import KNOBS

RESOLVER_AXIS = "resolvers"
# the SPMD step's own named_scopes, round conflict_step's (ops/conflict.py
# SCOPES): the clip of the batch to the shard, and the collectives after
STEP_SCOPES = ("clip", "combine")


def make_resolver_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the resolver key-partition axis."""
    if devices is None:
        devices = jax.devices()[: n_devices or len(jax.devices())]
    return Mesh(np.asarray(devices), (RESOLVER_AXIS,))


# A shard whose boundary count (an upper bound, see _fill_bound) passes this
# share of its capacity has the balance looked at before every step, not
# every RESOLUTION_BALANCE_CHECK_BATCHES steps: a quarter of the capacity is
# three full steps of point writes all landing on it.
FILL_SHED = 0.75
# rows of the load sample: quartiles of 4,096 keys are good to 0.7% of the
# keys, and four steps of the served shape turn the sample over, so that a
# move planned half a second into new traffic is planned from that traffic
LOAD_SAMPLE_ROWS = 4096
# a planned move is applied only if the sample says the busiest shard's share
# falls to this much of what it is: cuts that jitter move state for nothing
PLAN_GAIN = 0.9


def shard_cut_bytes(n_shards: int) -> list[bytes]:
    """Byte-space begin boundaries of the n equal key partitions
    (cuts[0] == b""); usable directly in host range maps."""
    return shard_cut_bytes_range(n_shards)


def shard_cut_bytes_range(n_shards: int, begin: bytes = b"",
                          end: bytes | None = None) -> list[bytes]:
    """Equal cuts of the resolver's OWNED range [begin, end) — the inner
    mesh split under an outer ResolverMap partition, and the cuts of a cold
    engine. cuts[0] stays b"": shard 0 also absorbs the sub-`begin` space an
    outer-partitioned resolver is never offered, so clipping stays total
    without a per-range ownership check. `end=None` means "to the end of
    keyspace". The cuts are as short as they can be: the range is read as an
    integer of 4, 8, .. KEY_BYTES bytes, the first width at which it can be
    cut n ways. A range narrower than n keys of KEY_BYTES bytes falls back to
    whole-space cuts (still correct: the extra shards sit on keyspace the
    resolver never sees)."""
    for width in range(4, keylib.KEY_BYTES + 1, 4):
        lo = int.from_bytes(begin[:width].ljust(width, b"\x00"), "big")
        hi = (1 << (8 * width)) if end is None else int.from_bytes(
            end[:width].ljust(width, b"\x00"), "big")
        if hi - lo >= n_shards:
            return [b""] + [
                (lo + (d * (hi - lo)) // n_shards).to_bytes(width, "big")
                for d in range(1, n_shards)]
    return shard_cut_bytes_range(n_shards)


def _cut_limbs(cut_bytes: list[bytes]) -> np.ndarray:
    """(n+1, L) limb vectors of n cuts: shard d owns [rows[d], rows[d+1]).
    Rows 0..n-1 are the exact encodings of the cut keys (so device-side limb
    comparisons agree with host byte-order comparisons for every key); the
    final sentinel is MAX (all-ones), after every real key."""
    n = len(cut_bytes)
    cuts = np.zeros((n + 1, L), dtype=np.uint32)
    for d, kb in enumerate(cut_bytes):
        cuts[d] = keylib.encode_key(kb)
    cuts[n, :] = 0xFFFFFFFF
    return cuts


def shard_cut_keys(n_shards: int) -> np.ndarray:
    """The limb vectors of the default equal cuts (see _cut_limbs)."""
    return _cut_limbs(shard_cut_bytes(n_shards))


def _sortable(rows: np.ndarray) -> np.ndarray:
    """(N, L) uint32 limb rows -> (N,) byte strings of 4L bytes that numpy
    compares, sorts and searches in the keys' own order (big-endian limbs;
    every row has the same width, so the comparison is memcmp)."""
    rows = np.ascontiguousarray(rows, dtype=np.uint32)
    return rows.astype(">u4").view(f"S{4 * rows.shape[1]}").ravel()


def plan_cuts(keys: np.ndarray, weights: np.ndarray | None,
              n_shards: int) -> list[bytes] | None:
    """Cut keys at the weighted quantiles of a sample of whole keys.

    `keys` is (N, L) uint32 limb rows (range begins), `weights` their mass
    (None = one each). Shard d+1 begins at the first distinct key at which
    the mass before it reaches d/n of the whole, so keys that share any
    prefix are split where they differ, down to the last byte. Declines
    (None) only when the mass sits on one key: that is a hot key, which no
    cut can divide. With fewer distinct keys than shards the spare cuts
    follow the last key at once (shards without keys; nothing better exists).
    """
    if len(keys) == 0:
        return None
    uniq, first, inverse = np.unique(_sortable(keys), return_index=True,
                                     return_inverse=True)
    if len(uniq) < 2:
        return None
    mass = np.bincount(inverse.ravel(), weights=weights, minlength=len(uniq))
    cum = np.cumsum(mass)
    if cum[-1] <= 0:
        return None
    cuts, j = [b""], 0
    for d in range(1, n_shards):
        # the first key past the one whose mass brings the sum to the target
        want = int(np.searchsorted(cum, cum[-1] * d / n_shards, "left")) + 1
        j = max(want, j + 1)
        if j < len(uniq):
            cuts.append(keylib.decode_key(keys[first[j]]))
        else:
            cuts.append(keylib.key_after(cuts[-1]))
    return cuts


def _shares(keys: np.ndarray, weights: np.ndarray | None,
            cut_bytes: list[bytes]) -> np.ndarray:
    """The share of the sample's mass that begins in each shard."""
    inner = _sortable(_cut_limbs(cut_bytes)[1:-1])
    idx = np.searchsorted(inner, _sortable(keys), "right")
    per = np.bincount(idx, weights=weights, minlength=len(cut_bytes))
    return per / max(per.sum(), 1e-300)


def _clip_ranges(b, e, lo, hi):
    """Intersect half-open ranges [b, e) (L, N) with shard range [lo, hi) (L,).

    Empty results (b' >= e') are exactly the ranges this shard does not own;
    conflict_step ignores empty ranges in every phase.
    """
    lo_b = jnp.broadcast_to(lo[:, None], b.shape)
    hi_b = jnp.broadcast_to(hi[:, None], e.shape)
    b2 = jnp.where(_key_lt(b, lo[:, None])[None, :], lo_b, b)
    e2 = jnp.where(_key_lt(hi[:, None], e)[None, :], hi_b, e)
    return b2, e2


@functools.lru_cache(maxsize=1)
def _compiled_vmapped_rebase():
    """Per-shard rebase, compiled once per process with the stacked state
    donated (delta is a traced scalar). The previous inline
    `jax.vmap(...)(core)` built a fresh traced callable on every rebase —
    a full re-trace per call, on top of keeping the dead pre-rebase state
    alive (devlint DEV002/DEV006)."""
    from foundationdb_tpu.ops.conflict import _donate_state_argnums
    return jax.jit(jax.vmap(rebase_state, in_axes=(0, None)),
                   donate_argnums=_donate_state_argnums())


@functools.lru_cache(maxsize=1)
def _compiled_table_builder():
    """Vmapped _build_table, compiled once per process. rebalance_cuts
    previously did `jax.jit(jax.vmap(_build_table))(...)` inline — a
    re-trace AND re-compile on every partition move (devlint DEV002)."""
    from foundationdb_tpu.ops.conflict import _build_table
    return jax.jit(jax.vmap(_build_table))


_STEP_CACHE: dict = {}


def sharded_conflict_step(mesh: Mesh, shapes: ConflictShapes,  # noqa: C901
                          max_write_life: int, intra_rounds: int = 0):
    key = (tuple(mesh.devices.flat), shapes, max_write_life, intra_rounds)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached
    fn = _build_sharded_step(mesh, shapes, max_write_life, intra_rounds)
    _STEP_CACHE[key] = fn
    return fn


def _build_sharded_step(mesh: Mesh, shapes: ConflictShapes,  # noqa: C901
                        max_write_life: int, intra_rounds: int = 0):
    """Build the jitted SPMD step: (stacked_state, batch) -> (state', statuses, info).

    stacked_state: state pytree with a leading n_shards axis, sharded over the
    mesh; batch: replicated (same encoding as conflict_step's batch). The
    shard's owned key range [lo, hi) is PART OF THE STATE (not baked into the
    program), so resolutionBalancing can re-cut the partition between batches
    without recompiling.
    """
    if shapes.key_bytes != keylib.KEY_BYTES:
        raise ValueError(
            f"sharded engine only supports the default key width "
            f"({keylib.KEY_BYTES}B); got key_bytes={shapes.key_bytes}. "
            "Thread shapes.limbs through shard_cut_keys/_clip_ranges to "
            "narrow it.")

    def local_step(state, batch):
        state = jax.tree.map(lambda x: x[0], state)  # drop leading shard dim
        lo = state.pop("lo")
        hi = state.pop("hi")
        batch = dict(batch)
        with jax.named_scope("clip"):
            batch["rb"], batch["re"] = _clip_ranges(
                batch["rb"], batch["re"], lo, hi)
            batch["wb"], batch["we"] = _clip_ranges(
                batch["wb"], batch["we"], lo, hi)
        new_state, statuses, info = conflict_step(
            state, batch, shapes=shapes, max_write_life=max_write_life,
            intra_rounds=intra_rounds)
        new_state["lo"] = lo
        new_state["hi"] = hi
        with jax.named_scope("combine"):
            # proxy combine: min over shards
            # (MasterProxyServer.actor.cpp:492-504)
            statuses = lax.pmin(statuses, RESOLVER_AXIS)
            info = {
                "overflow": lax.pmax(info["overflow"], RESOLVER_AXIS),
                "boundaries": lax.pmax(info["boundaries"], RESOLVER_AXIS),
                "evicted": lax.psum(info["evicted"], RESOLVER_AXIS),
                # every shard's boundary count, for the host's balance
                "fill": lax.all_gather(new_state["nb"], RESOLVER_AXIS),
                # mask padding slots (forced COMMITTED inside conflict_step)
                "committed": jnp.sum((statuses == 2) & batch["txn_valid"]),
                # the sharded engine always runs full sandwich rounds (see
                # ShardedDeviceConflictSet: the host fallback can't
                # reproduce per-shard intra semantics), so this stays True;
                # combined defensively anyway
                "converged": lax.pmin(
                    info["converged"].astype(jnp.int32), RESOLVER_AXIS) > 0,
                # eligible on every shard — only consulted by the
                # (never-taken) fallback path
                "eligible": lax.pmin(
                    info["eligible"].astype(jnp.int32), RESOLVER_AXIS) > 0,
            }
        return jax.tree.map(lambda x: x[None], new_state), statuses, info

    state_specs = {
        "bkeys": P(RESOLVER_AXIS), "bval": P(RESOLVER_AXIS),
        "nb": P(RESOLVER_AXIS), "oldest": P(RESOLVER_AXIS),
        "table": P(RESOLVER_AXIS), "poisoned": P(RESOLVER_AXIS),
        "lo": P(RESOLVER_AXIS), "hi": P(RESOLVER_AXIS),
    }
    batch_specs = {
        "rb": P(), "re": P(), "rtxn": P(), "wb": P(), "we": P(), "wtxn": P(),
        "snapshot": P(), "txn_valid": P(), "commit_version": P(),
        "advance_floor": P(),
    }
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, batch_specs),
        out_specs=(state_specs, P(), {"overflow": P(), "boundaries": P(),
                                      "evicted": P(),
                                      "fill": P(), "committed": P(),
                                      "converged": P(), "eligible": P()}),
        # conflict_step's bounded-scan carries start from unvarying constants
        # and become shard-varying inside the loop; the static replication /
        # VMA check can't type that, so it is disabled (collectives are only
        # pmin/pmax).
        check_vma=False,
    )
    from foundationdb_tpu.ops.conflict import _donate_state_argnums, _named
    # under the step's own name: a profile calls it `jit_conflict_step`, and
    # what reads the one-chip step's time and scopes reads this one
    return jax.jit(_named(sharded, "conflict_step"),
                   donate_argnums=_donate_state_argnums())


def init_sharded_state(shapes: ConflictShapes, n_shards: int, oldest: int = 0,
                       cut_bytes: list[bytes] | None = None,
                       mesh: Mesh | None = None):
    """Stacked per-shard initial states, leading axis = shard. Each shard
    carries its owned range [lo, hi) as state (dynamic cuts).

    Pass `mesh` to place the state with the step's sharding up front:
    default-placed leaves make jit specialize the first step call on the
    unsharded layout and RE-specialize on its own mesh-sharded output — a
    second full XLA compile that would otherwise land on the first SERVED
    batch (warmup only pays for one)."""
    one = init_state(shapes, oldest=oldest)
    st = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_shards,) + x.shape), one)
    cuts = _cut_limbs(cut_bytes or shard_cut_bytes(n_shards))
    st["lo"] = jnp.asarray(cuts[:n_shards])
    st["hi"] = jnp.asarray(cuts[1:])
    if mesh is not None:
        from jax.sharding import NamedSharding

        from foundationdb_tpu.utils import jaxenv
        st = jaxenv.device_put(st, NamedSharding(mesh, P(RESOLVER_AXIS)))
    return st


class ShardedDeviceConflictSet:
    """Multi-device ConflictSet: same host interface as DeviceConflictSet,
    state sharded by key range over a mesh (one logical resolver spanning
    devices — the reference's N-resolver topology collapsed into one SPMD
    program; Resolver.actor.cpp ordering/recovery semantics live in the host
    Resolver role unchanged).
    """

    def __init__(self, mesh: Mesh | None = None, capacity: int | None = None,
                 txns: int | None = None, reads_per_txn: int | None = None,
                 writes_per_txn: int | None = None, oldest_version: int = 0,
                 cut_bytes: list[bytes] | None = None):
        from foundationdb_tpu.ops.conflict import (
            BatchEncoder, _resolve_shapes, install_profiler_annotator)
        install_profiler_annotator()
        self.mesh = mesh or make_resolver_mesh()
        self.n_shards = self.mesh.devices.size
        self.shapes = _resolve_shapes(capacity, txns, reads_per_txn, writes_per_txn)
        self.encoder = BatchEncoder(self.shapes, base_version=oldest_version)
        self.oldest_version = oldest_version
        # full sandwich rounds (T//2+1): the host-exact fallback resolves
        # intra conflicts with SINGLE-resolver semantics, which per-shard
        # "earlier txns win" + pmin does not reduce to, so the sharded
        # engine must always converge on device. The early-out cond makes
        # the unused rounds ~free once the bounds pinch.
        self._step = sharded_conflict_step(
            self.mesh, self.shapes, KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS,
            self.shapes.txns // 2 + 1)
        # resolutionBalancing's observations (module docstring)
        self._sample = np.zeros((LOAD_SAMPLE_ROWS, L), dtype=np.uint32)
        self._sample_rng = np.random.RandomState(0)
        # cuts scheduled by rebalance_from_conflicts, applied before the next
        # step (the dispatch thread owns all state restructures)
        self._pending_cuts: list[bytes] | None = None
        # what the resolver role reports: moves applied, ranges that clipped
        # non-empty summed over shards, and the busiest shard's count summed
        # over steps
        self.rebalances = 0
        self.ranges_offered = 0
        self.ranges_fullest = 0
        self._set_cuts(list(cut_bytes or shard_cut_bytes(self.n_shards)))
        self._state = init_sharded_state(self.shapes, self.n_shards, oldest=0,
                                         cut_bytes=self.cut_bytes,
                                         mesh=self.mesh)

    def _set_cuts(self, cut_bytes: list[bytes], fill=None):
        """New cuts: what was observed under the old ones says nothing about
        these shards. `fill` is the shards' boundary counts, where known."""
        assert cut_bytes[0] == b"" and len(cut_bytes) == self.n_shards
        self.cut_bytes = cut_bytes
        self._inner_cuts = _sortable(_cut_limbs(cut_bytes)[1:-1])
        self._offered = np.zeros(self.n_shards, dtype=np.int64)
        self._n_sample = 0
        self._steps_since_check = 0
        # the boundary counts last read from a step, the step's output that
        # is on its way, and the write ranges offered since each
        self._fill = (np.ones(self.n_shards, np.int64) if fill is None
                      else np.asarray(fill, np.int64))
        self._fill_pending = None
        self._writes_since_read = np.zeros(self.n_shards, dtype=np.int64)
        self._writes_since_pending = np.zeros(self.n_shards, dtype=np.int64)

    @property
    def base_version(self) -> int:
        return self.encoder.base_version

    @property
    def fill_fullest(self) -> int:
        """The fullest shard's boundaries as last read from a step."""
        return int(self._fill.max())

    def _maybe_rebase(self, commit_version: int):
        while commit_version - self.encoder.base_version > _REBASE_THRESHOLD:
            delta = min(commit_version - self.encoder.base_version - (1 << 24),
                        1 << 30)
            lo, hi = self._state["lo"], self._state["hi"]
            core = {k: v for k, v in self._state.items()
                    if k not in ("lo", "hi")}
            core = _compiled_vmapped_rebase()(core, np.int32(delta))
            core["lo"], core["hi"] = lo, hi
            self._state = core
            self.encoder.base_version += delta

    def plan_chunk(self, nr: int, nw: int):
        """Mesh program is fixed (sharding specs bake the shapes): no
        bucketed padding here, unlike the single-device engine. The step
        handed out is the compiled one behind the balance's eyes."""
        return self.shapes, self._balanced_step

    def warmup(self):
        self.detect([], self.encoder.base_version + 1)
        # a move's table build compiles now, not under the first move
        _compiled_table_builder()(self._state["bval"]).block_until_ready()

    def write_scope_maps(self, directory: str) -> None:
        """`scopes.conflict_step.<reads>x<writes>.json`, as the one-chip
        engine writes for each of its bucket programs: which scope each
        instruction of the compiled SPMD program runs under, `clip` and
        `combine` beside conflict_step's own."""
        from foundationdb_tpu.ops.conflict import SCOPES, write_scope_map
        batch = self.encoder.encode_batch([], self.encoder.base_version + 1)
        write_scope_map(
            directory, self.shapes,
            self._step.lower(self._state, batch).compile().as_text(),
            SCOPES + STEP_SCOPES)

    def detect(self, txns: list[TxnConflictInfo], commit_version: int) -> list[int]:
        return self.detect_async(txns, commit_version).result()

    def detect_async(self, txns: list[TxnConflictInfo], commit_version: int):
        from foundationdb_tpu.ops.conflict import detect_async_impl
        return detect_async_impl(self, txns, commit_version)

    def clear(self, oldest_version: int = 0):
        self.encoder.base_version = oldest_version
        self.oldest_version = oldest_version
        self._state = init_sharded_state(self.shapes, self.n_shards, oldest=0,
                                         cut_bytes=self.cut_bytes,
                                         mesh=self.mesh)
        # stale load/samples must not drive a rebalance of the fresh state
        self._set_cuts(self.cut_bytes)
        self._pending_cuts = None

    # -- resolutionBalancing --

    def _balanced_step(self, state, batch):
        """One step of the compiled program, with the balance before it:
        the batch's ranges are counted, and if the counts say so the cuts
        move first (the dispatch thread owns the state, and between two
        steps nothing is in flight on it that a move could tear)."""
        self._observe(batch)
        at_version = self.encoder.base_version + int(batch["commit_version"])
        if self._balance(at_version):
            state = self._state
        new_state, statuses, info = self._step(state, batch)
        fill = info.pop("fill")
        if self._fill_pending is None:
            self._fill_pending = fill
            self._writes_since_pending[:] = 0
            fill.copy_to_host_async()
        return new_state, statuses, info

    def _observe(self, batch: dict):
        """Count the encoded batch's ranges per shard and sample their begin
        keys: numpy over the limbs the encoder made, no Python per range."""
        # both encoders fill a buffer's columns from the first on, and mark
        # the rest with the transaction number T
        T = self.shapes.txns
        nr = int(np.count_nonzero(batch["rtxn"] < T))
        nw = int(np.count_nonzero(batch["wtxn"] < T))
        if not nr + nw:
            return
        begins = np.concatenate([batch["rb"][:, :nr].T, batch["wb"][:, :nw].T])
        sb = _sortable(begins)
        se = _sortable(np.concatenate(
            [batch["re"][:, :nr].T, batch["we"][:, :nw].T]))
        # a range is offered to the shards from its begin's to the one that
        # holds the last key before its end
        live = sb < se
        first = np.searchsorted(self._inner_cuts, sb, "right")[live]
        last = np.searchsorted(self._inner_cuts, se, "left")[live]
        n = self.n_shards

        def per_shard(sel: slice):
            return np.cumsum(
                np.bincount(first[sel], minlength=n + 1)
                - np.bincount(last[sel] + 1, minlength=n + 2)[:n + 1])[:n]

        live_reads = int(np.count_nonzero(live[:nr]))
        writes = per_shard(slice(live_reads, None))
        offered = per_shard(slice(0, live_reads)) + writes
        self._offered += offered
        self.ranges_offered += int(offered.sum())
        self.ranges_fullest += int(offered.max())
        self._writes_since_read += writes
        self._writes_since_pending += writes
        # the reservoir: filled in order, then each new key takes a random
        # row, so it forgets at the rate it is fed
        room = min(LOAD_SAMPLE_ROWS - self._n_sample, len(begins))
        self._sample[self._n_sample:self._n_sample + room] = begins[:room]
        self._n_sample += room
        rest = begins[room:]
        if len(rest):
            self._sample[self._sample_rng.randint(
                0, LOAD_SAMPLE_ROWS, size=len(rest))] = rest

    def _fill_bound(self) -> np.ndarray:
        """No shard holds more boundaries than this after the step about to
        run: the counts the last finished step reported, plus two for every
        write range offered since."""
        pending = self._fill_pending
        if pending is not None and pending.is_ready():
            from foundationdb_tpu.utils import jaxenv
            jaxenv.count_device_get(pending)
            self._fill = np.asarray(pending, dtype=np.int64)
            self._writes_since_read[:] = self._writes_since_pending
            self._fill_pending = None
        return self._fill + 2 * self._writes_since_read

    def _balance(self, at_version: int) -> bool:
        """Move the cuts if it is time to look and the look says so."""
        if self._pending_cuts is not None:
            cuts, self._pending_cuts = self._pending_cuts, None
            if cuts != self.cut_bytes:
                self.rebalance_cuts(cuts, at_version)
                return True
        self._steps_since_check += 1
        if (self._steps_since_check >= KNOBS.RESOLUTION_BALANCE_CHECK_BATCHES
                or self._fill_bound().max()
                > FILL_SHED * self.shapes.capacity):
            return self.maybe_rebalance(at_version)
        return False

    def _plan(self, keys, weights, load) -> list[bytes] | None:
        """New cuts from a sample of whole keys, if `load` (mass per shard
        under the cuts in force) is skewed and the plan mends it."""
        if load.max() <= KNOBS.RESOLUTION_BALANCE_SKEW * load.mean():
            return None
        cuts = plan_cuts(keys, weights, self.n_shards)
        if cuts is None or cuts == self.cut_bytes:
            return None
        now = _shares(keys, weights, self.cut_bytes).max()
        if _shares(keys, weights, cuts).max() > PLAN_GAIN * now:
            return None
        return cuts

    def maybe_rebalance(self, at_version: int) -> bool:
        """Re-cut the key partition when per-shard load skews (the between-
        steps analogue of masterserver resolutionBalancing: sampled load ->
        new cuts -> state restructure). Returns True if a rebalance ran."""
        if (self._offered.sum() < KNOBS.RESOLUTION_BALANCE_MIN_SAMPLES
                or self._n_sample < self.n_shards * 4):
            return False
        # a look uses the counts up: the next one waits for as many again
        load, self._offered = self._offered, np.zeros_like(self._offered)
        self._steps_since_check = 0
        cuts = self._plan(self._sample[:self._n_sample], None, load)
        if cuts is None:
            return False
        self.rebalance_cuts(cuts, at_version)
        return True

    def rebalance_from_conflicts(self, ranges) -> bool:
        """Conflict-mass-driven recut, the cross-epoch resolutionBalancing
        analogue: `ranges` is [(begin, end, rate)] from the resolver role's
        HotRangeSketch — per-range exponentially-decayed CONFLICT mass.
        Where maybe_rebalance recuts on raw read/write traffic, this path
        recuts on where aborts actually land, so a conflict-hot shard sheds
        keyspace even when range counts look balanced.

        Pure host numpy: it only PLANS and schedules the cuts (safe to call
        from the resolver's event loop — no device sync, devlint DEV001);
        the next step applies the restructure on the dispatch path, so cuts
        never move under an in-flight batch. Same planner and same safety
        story as the load path: rebalance_cuts's conservative fill can only
        create false conflicts. Returns True iff a recut was scheduled."""
        if not ranges:
            return False
        keys = np.stack([keylib.encode_key(b) for b, _e, _r in ranges])
        mass = np.array([r for _b, _e, r in ranges], dtype=np.float64)
        if mass.sum() <= 0.0:
            return False
        cuts = self._plan(keys, mass, _shares(keys, mass, self.cut_bytes))
        if cuts is None:
            return False
        self._pending_cuts = cuts
        return True

    def rebalance_cuts(self, new_cut_bytes: list[bytes], at_version: int):
        """Move the partition to `new_cut_bytes`. Conflict state is SOFT
        (clearConflictSet semantics, SkipList.cpp:957): a shard's newly
        acquired subranges are filled at `at_version` — conservative-only
        (stale reads there conflict; never a false commit) — while retained
        subranges keep exact history. No cross-shard state movement, no
        recompilation (cuts are state, not program constants). It waits for
        every step in flight, brings keys and values to the host and puts
        the new ones back, on the dispatch path: `Resolver.Recut` times it."""
        from foundationdb_tpu.utils.trace import g_trace_batch
        clock = getattr(self, "trace_clock", None) or time.monotonic
        with g_trace_batch.section("CommitSpan", f"v{at_version}",
                                   "Resolver.Recut", now=clock):
            self._move_cuts(list(new_cut_bytes), at_version)

    def _move_cuts(self, new_cut_bytes: list[bytes], at_version: int):
        from jax.sharding import NamedSharding

        from foundationdb_tpu.utils import jaxenv

        assert len(new_cut_bytes) == self.n_shards and new_cut_bytes[0] == b""
        K = self.shapes.capacity
        st = jaxenv.device_get({k: self._state[k] for k in (
            "bkeys", "bval", "nb", "lo", "hi")})
        vfill = np.int32(self.encoder._clamp_off(at_version))
        cuts = _cut_limbs(new_cut_bytes)

        new_bkeys = np.full_like(st["bkeys"], 0xFFFFFFFF)
        new_bval = np.full_like(st["bval"], int(NEG))
        new_nb = np.zeros_like(st["nb"])
        # the byte strings order as the keys do (_sortable): row d of each
        old_lo, old_hi = _sortable(st["lo"]), _sortable(st["hi"])
        new_lo, new_hi = _sortable(cuts[:-1]), _sortable(cuts[1:])
        for d in range(self.n_shards):
            lo, hi = new_lo[d], new_hi[d]
            # what the shard keeps: [a, b), its old range within its new one
            a_row, a = ((cuts[d], lo) if old_lo[d] <= lo
                        else (st["lo"][d], old_lo[d]))
            b_row, b = ((cuts[d + 1], hi) if hi <= old_hi[d]
                        else (st["hi"][d], old_hi[d]))
            nb = int(st["nb"][d])
            keys_d = st["bkeys"][d][:, :nb]  # (L, nb), in order
            vals_d = st["bval"][d][:nb]
            sk = _sortable(keys_d.T)
            fill = np.asarray([vfill], np.int32)
            if a < b:
                # the value in effect at `a` is the last boundary's <= a;
                # the boundaries inside (a, b) stay as they are
                i_a = int(np.searchsorted(sk, a, "right"))
                i_b = int(np.searchsorted(sk, b, "left"))
                at_a = vals_d[i_a - 1] if i_a else np.int32(NEG)
                out_k = [a_row[:, None], keys_d[:, i_a:i_b]]
                out_v = [np.asarray([at_a], np.int32), vals_d[i_a:i_b]]
                if lo < a:  # acquired below: [lo, a)
                    out_k.insert(0, cuts[d][:, None])
                    out_v.insert(0, fill)
                if b < hi:  # acquired above: [b, hi)
                    out_k.append(b_row[:, None])
                    out_v.append(fill)
            else:  # nothing kept: the whole new range is acquired
                out_k, out_v = [cuts[d][:, None]], [fill]
            kcat = np.concatenate(out_k, axis=1)
            vcat = np.concatenate(out_v)
            if kcat.shape[1] > K:
                # cannot represent: collapse to fully conservative (safe)
                kcat, vcat = cuts[d][:, None], fill
            n = kcat.shape[1]
            new_bkeys[d, :, :n] = kcat
            new_bval[d, :n] = vcat
            new_nb[d] = n

        sharding = NamedSharding(self.mesh, P(RESOLVER_AXIS))
        bval_dev = jaxenv.device_put(new_bval, sharding)
        self._state = {
            "bkeys": jaxenv.device_put(new_bkeys, sharding),
            "bval": bval_dev,
            "nb": jaxenv.device_put(new_nb, sharding),
            "oldest": self._state["oldest"],
            "table": _compiled_table_builder()(bval_dev),
            "poisoned": self._state["poisoned"],
            "lo": jaxenv.device_put(cuts[: self.n_shards], sharding),
            "hi": jaxenv.device_put(cuts[1:], sharding),
        }
        self._set_cuts(new_cut_bytes, fill=new_nb)
        self.rebalances += 1
