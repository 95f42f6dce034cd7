"""Key-partitioned conflict engine over a device mesh (SPMD via shard_map).

TPU-native analogue of the reference's multi-resolver scale-out (SURVEY.md
§2.0): the proxy splits every transaction's conflict ranges across resolvers
by a key-range map (MasterProxyServer.actor.cpp:283-306) and a transaction
commits only if every touched resolver said Committed — the proxy takes the
min over resolver verdicts (:492-504). Here each mesh device IS one resolver
shard:

- The versioned step-function state lives sharded along a `resolvers` mesh
  axis; shard d owns keys in [cut_d, cut_{d+1}) (static equal cuts of the
  uint32 first-limb space — the dynamic resolutionBalancing analogue rebalances
  cuts between epochs, not inside the jitted step).
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
"""

from __future__ import annotations

import functools

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


def make_resolver_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the resolver key-partition axis."""
    if devices is None:
        devices = jax.devices()[: n_devices or len(jax.devices())]
    return Mesh(np.asarray(devices), (RESOLVER_AXIS,))


def shard_cut_bytes(n_shards: int) -> list[bytes]:
    """Byte-space begin boundaries of the n equal key partitions
    (cuts[0] == b""); usable directly in host range maps."""
    return [b""] + [((d * (1 << 32)) // n_shards).to_bytes(4, "big")
                    for d in range(1, n_shards)]


def shard_cut_bytes_range(n_shards: int, begin: bytes = b"",
                          end: bytes | None = None) -> list[bytes]:
    """Equal cuts of the resolver's OWNED range [begin, end) — the inner
    mesh split under an outer ResolverMap partition. cuts[0] stays b"":
    shard 0 also absorbs the sub-`begin` space an outer-partitioned
    resolver is never offered, so clipping stays total without a per-range
    ownership check. `end=None` means "to the end of keyspace". Falls back
    to whole-space cuts when the range is too narrow to cut n ways at
    4-byte granularity (degenerate, but still correct: extra shards just
    sit idle on keyspace the resolver never sees)."""
    lo = int.from_bytes(begin[:4].ljust(4, b"\x00"), "big")
    hi = (1 << 32) if end is None else int.from_bytes(
        end[:4].ljust(4, b"\x00"), "big")
    if hi - lo < n_shards:
        return shard_cut_bytes(n_shards)
    return [b""] + [(lo + (d * (hi - lo)) // n_shards).to_bytes(4, "big")
                    for d in range(1, n_shards)]


def shard_cut_keys(n_shards: int) -> np.ndarray:
    """(n_shards+1, L) limb vectors: shard d owns [cuts[d], cuts[d+1]).

    Rows 0..n-1 are the exact encodings of shard_cut_bytes (so device-side
    limb comparisons agree with host byte-order comparisons for every key);
    the final sentinel is MAX (all-ones), after every real key.
    """
    from foundationdb_tpu.utils import keys as keylib

    cuts = np.zeros((n_shards + 1, L), dtype=np.uint32)
    for d, kb in enumerate(shard_cut_bytes(n_shards)):
        cuts[d] = keylib.encode_key(kb)
    cuts[n_shards, :] = 0xFFFFFFFF
    return cuts


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
                          max_write_life: int, intra_mode: str = "scan",
                          intra_rounds: int = 0):
    key = (tuple(mesh.devices.flat), shapes, max_write_life, intra_mode,
           intra_rounds)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached
    fn = _build_sharded_step(mesh, shapes, max_write_life, intra_mode,
                             intra_rounds)
    _STEP_CACHE[key] = fn
    return fn


def _build_sharded_step(mesh: Mesh, shapes: ConflictShapes,  # noqa: C901
                        max_write_life: int, intra_mode: str = "scan",
                        intra_rounds: int = 0):
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
        batch["rb"], batch["re"] = _clip_ranges(batch["rb"], batch["re"], lo, hi)
        batch["wb"], batch["we"] = _clip_ranges(batch["wb"], batch["we"], lo, hi)
        new_state, statuses, info = conflict_step(
            state, batch, shapes=shapes, max_write_life=max_write_life,
            intra_mode=intra_mode, intra_rounds=intra_rounds)
        new_state["lo"] = lo
        new_state["hi"] = hi
        # proxy combine: min over shards (MasterProxyServer.actor.cpp:492-504)
        statuses = lax.pmin(statuses, RESOLVER_AXIS)
        info = {
            "overflow": lax.pmax(info["overflow"], RESOLVER_AXIS),
            "boundaries": lax.pmax(info["boundaries"], RESOLVER_AXIS),
            # mask padding slots (forced COMMITTED inside conflict_step)
            "committed": jnp.sum((statuses == 2) & batch["txn_valid"]),
            # the sharded engine always runs full sandwich rounds (see
            # ShardedDeviceConflictSet: the host fallback can't reproduce
            # per-shard intra semantics), so this stays True; combined
            # defensively anyway
            "converged": lax.pmin(
                info["converged"].astype(jnp.int32), RESOLVER_AXIS) > 0,
            # eligible on every shard — only consulted by the (never-taken)
            # fallback path
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
                                      "committed": P(), "converged": P(),
                                      "eligible": P()}),
        # conflict_step's bounded-scan carries start from unvarying constants
        # and become shard-varying inside the loop; the static replication /
        # VMA check can't type that, so it is disabled (collectives are only
        # pmin/pmax).
        check_vma=False,
    )
    from foundationdb_tpu.ops.conflict import _donate_state_argnums
    return jax.jit(sharded, donate_argnums=_donate_state_argnums())


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
    cuts = np.zeros((n_shards + 1, L), dtype=np.uint32)
    for d, kb in enumerate(cut_bytes or shard_cut_bytes(n_shards)):
        cuts[d] = keylib.encode_key(kb)
    cuts[n_shards, :] = 0xFFFFFFFF
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
        self.cut_bytes = list(cut_bytes or shard_cut_bytes(self.n_shards))
        assert self.cut_bytes[0] == b"" and len(self.cut_bytes) == self.n_shards
        self._state = init_sharded_state(self.shapes, self.n_shards, oldest=0,
                                         cut_bytes=self.cut_bytes,
                                         mesh=self.mesh)
        # full sandwich rounds (T//2+1): the host-exact fallback resolves
        # intra conflicts with SINGLE-resolver semantics, which per-shard
        # "earlier txns win" + pmin does not reduce to, so the sharded
        # engine must always converge on device. The early-out cond makes
        # the unused rounds ~free once the bounds pinch.
        intra_rounds = (self.shapes.txns // 2 + 1
                        if str(KNOBS.CONFLICT_INTRA_MODE) == "scan" else 0)
        self._step = sharded_conflict_step(
            self.mesh, self.shapes, KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS,
            str(KNOBS.CONFLICT_INTRA_MODE), intra_rounds)
        # resolutionBalancing inputs (masterserver.actor.cpp:955-1012 via
        # Resolver iops sampling :146-151): per-shard range counts + a
        # bounded reservoir of range-begin prefixes
        self._load_counts = np.zeros(self.n_shards, dtype=np.int64)
        self._samples: list[int] = []  # first-4-byte ints of range begins
        self._batches_since_check = 0
        # cuts scheduled by rebalance_from_conflicts, applied by the next
        # detect_async (the dispatch thread owns all state restructures)
        self._pending_cuts: list[bytes] | None = None
        self._sample_rng = np.random.RandomState(0)
        self.rebalances = 0

    @property
    def base_version(self) -> int:
        return self.encoder.base_version

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
        bucketed padding here, unlike the single-device engine."""
        return self.shapes, self._step

    def warmup(self):
        self.detect([], self.encoder.base_version + 1)

    def detect(self, txns: list[TxnConflictInfo], commit_version: int) -> list[int]:
        return self.detect_async(txns, commit_version).result()

    def detect_async(self, txns: list[TxnConflictInfo], commit_version: int):
        from foundationdb_tpu.ops.conflict import detect_async_impl

        if self._pending_cuts is not None:
            cuts, self._pending_cuts = self._pending_cuts, None
            if cuts != self.cut_bytes:
                self.rebalance_cuts(cuts, commit_version)
        self._record_load(txns)
        self._batches_since_check += 1
        if self._batches_since_check >= KNOBS.RESOLUTION_BALANCE_CHECK_BATCHES:
            self._batches_since_check = 0
            self.maybe_rebalance(commit_version)
        return detect_async_impl(self, txns, commit_version)

    def clear(self, oldest_version: int = 0):
        self.encoder.base_version = oldest_version
        self.oldest_version = oldest_version
        self._state = init_sharded_state(self.shapes, self.n_shards, oldest=0,
                                         cut_bytes=self.cut_bytes,
                                         mesh=self.mesh)
        # stale load/samples must not drive a rebalance of the fresh state
        self._load_counts[:] = 0
        self._samples.clear()
        self._batches_since_check = 0
        self._pending_cuts = None

    # -- resolutionBalancing --

    def _record_load(self, txns):
        """One vectorized pass per batch (this rides the resolver hot path:
        per-range Python would cost as much as the device step itself)."""
        begins = [b for t in txns for b, _e in t.read_ranges]
        wbegins = [b for t in txns for b, _e in t.write_ranges]
        if not begins and not wbegins:
            return
        prefixes = np.array(
            [int.from_bytes(b[:4].ljust(4, b"\x00"), "big")
             for b in begins + wbegins], dtype=np.uint64)
        cut_pref = np.array(
            [int.from_bytes(cb[:4].ljust(4, b"\x00"), "big")
             for cb in self.cut_bytes], dtype=np.uint64)
        shard_idx = np.searchsorted(cut_pref, prefixes, side="right") - 1
        np.add.at(self._load_counts, shard_idx, 1)
        wpref = prefixes[len(begins):]
        cap = 8192
        room = cap - len(self._samples)
        if room > 0:
            self._samples.extend(wpref[:room].tolist())
            wpref = wpref[room:]
        if len(wpref):
            js = self._sample_rng.randint(0, cap, size=len(wpref))
            for j, v in zip(js.tolist(), wpref.tolist()):
                self._samples[j] = v

    def maybe_rebalance(self, at_version: int) -> bool:
        """Re-cut the key partition when per-shard load skews (the between-
        batches analogue of masterserver resolutionBalancing: sampled load ->
        new cuts -> state restructure). Returns True if a rebalance ran."""
        total = int(self._load_counts.sum())
        if (total < KNOBS.RESOLUTION_BALANCE_MIN_SAMPLES
                or len(self._samples) < self.n_shards * 4):
            return False
        mean = total / self.n_shards
        if self._load_counts.max() <= KNOBS.RESOLUTION_BALANCE_SKEW * mean:
            return False
        qs = np.quantile(np.asarray(self._samples, dtype=np.float64),
                         [d / self.n_shards for d in range(1, self.n_shards)])
        new_cuts = [b""]
        for q in qs:
            cb = int(min(max(q, 0), (1 << 32) - 1)).to_bytes(4, "big")
            if cb <= new_cuts[-1]:
                return False  # degenerate sample (mass on one prefix): keep cuts
            new_cuts.append(cb)
        self.rebalance_cuts(new_cuts, at_version)
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
        detect_async applies the restructure at the next batch boundary on
        the dispatch path, so cuts never move under an in-flight batch.
        Same safety story as the load path: rebalance_cuts's conservative
        fill can only create false conflicts. Returns True iff a recut was
        scheduled."""
        if not ranges:
            return False
        prefs = np.array(
            [int.from_bytes(b[:4].ljust(4, b"\x00"), "big")
             for b, _e, _r in ranges], dtype=np.float64)
        mass = np.array([r for _b, _e, r in ranges], dtype=np.float64)
        total = float(mass.sum())
        if total <= 0.0:
            return False
        cut_pref = np.array(
            [int.from_bytes(cb[:4].ljust(4, b"\x00"), "big")
             for cb in self.cut_bytes], dtype=np.float64)
        shard_idx = np.searchsorted(cut_pref, prefs, side="right") - 1
        per_shard = np.zeros(self.n_shards, dtype=np.float64)
        np.add.at(per_shard, shard_idx, mass)
        skew = KNOBS.RESOLUTION_BALANCE_SKEW * (total / self.n_shards)
        if per_shard.max() <= skew:
            return False
        # weighted-quantile cuts: sort hot ranges by key prefix, cut where
        # cumulative conflict mass crosses each d/n target
        order = np.argsort(prefs, kind="stable")
        cum = np.cumsum(mass[order])
        targets = [total * d / self.n_shards
                   for d in range(1, self.n_shards)]
        idxs = np.searchsorted(cum, targets, side="left")
        sorted_prefs = prefs[order]
        new_cuts = [b""]
        for i in idxs:
            i = min(int(i), len(order) - 1)
            cb = int(sorted_prefs[i]).to_bytes(4, "big")
            while cb <= new_cuts[-1]:
                # target landed on/behind the previous cut (mass front-
                # loaded on few ranges): advance to the next distinct hot
                # prefix so a dominant range still gets isolated. Running
                # out means the mass sits on ONE prefix — a DD shard-split
                # problem, not a resolver cut problem; keep the cuts.
                i += 1
                if i >= len(order):
                    return False
                cb = int(sorted_prefs[i]).to_bytes(4, "big")
            new_cuts.append(cb)
        if new_cuts == self.cut_bytes:
            return False
        self._pending_cuts = new_cuts
        return True

    def rebalance_cuts(self, new_cut_bytes: list[bytes], at_version: int):
        """Move the partition to `new_cut_bytes`. Conflict state is SOFT
        (clearConflictSet semantics, SkipList.cpp:957): a shard's newly
        acquired subranges are filled at `at_version` — conservative-only
        (stale reads there conflict; never a false commit) — while retained
        subranges keep exact history. No cross-shard state movement, no
        recompilation (cuts are state, not program constants)."""
        from jax.sharding import NamedSharding

        from foundationdb_tpu.utils import jaxenv

        assert len(new_cut_bytes) == self.n_shards and new_cut_bytes[0] == b""
        K = self.shapes.capacity
        st = jaxenv.device_get(self._state)
        vfill = np.int32(self.encoder._clamp_off(at_version))

        cuts = np.zeros((self.n_shards + 1, L), dtype=np.uint32)
        for d, kb in enumerate(new_cut_bytes):
            cuts[d] = keylib.encode_key(kb)
        cuts[self.n_shards, :] = 0xFFFFFFFF

        old_lo, old_hi = st["lo"], st["hi"]  # (n, L)
        nb = st["nb"]
        new_bkeys = np.full_like(st["bkeys"], 0xFFFFFFFF)
        new_bval = np.full_like(st["bval"], int(NEG))
        new_nb = np.zeros_like(nb)

        def np_lt1(a, b):  # lexicographic a < b over (L,) uint32
            for i in range(L):
                if a[i] != b[i]:
                    return a[i] < b[i]
            return False

        def np_cmp_vec(keys, q):  # (L, N) keys vs (L,) q -> (lt, eq) masks
            lt = np.zeros(keys.shape[1], bool)
            eq = np.ones(keys.shape[1], bool)
            for i in range(L):
                lt |= eq & (keys[i] < q[i])
                eq &= keys[i] == q[i]
            return lt, eq

        for d in range(self.n_shards):
            lo, hi = cuts[d], cuts[d + 1]
            a = old_lo[d] if np_lt1(lo, old_lo[d]) else lo  # retained begin
            b = old_hi[d] if np_lt1(hi, old_hi[d]) else hi  # retained end
            keys_d = st["bkeys"][d]  # (L, K)
            vals_d = st["bval"][d]
            live = np.arange(K) < int(nb[d])
            out_k: list[np.ndarray] = []  # (L, ni) pieces
            out_v: list[np.ndarray] = []
            if np_lt1(a, b):  # retained interval non-empty
                if np_lt1(lo, a):  # acquired prefix [lo, a)
                    out_k.append(lo[:, None])
                    out_v.append(np.asarray([vfill], np.int32))
                # value in effect at `a` = last live boundary <= a
                lt_a, eq_a = np_cmp_vec(keys_d, a)
                le_a = live & (lt_a | eq_a)
                n_le = int(le_a.sum())
                at_a = int(vals_d[n_le - 1]) if n_le else int(NEG)
                out_k.append(a[:, None])
                out_v.append(np.asarray([at_a], np.int32))
                lt_b, _ = np_cmp_vec(keys_d, b)
                interior = live & ~(lt_a | eq_a) & lt_b
                out_k.append(keys_d[:, interior])
                out_v.append(vals_d[interior])
                if np_lt1(b, hi):  # acquired suffix [b, hi)
                    out_k.append(b[:, None])
                    out_v.append(np.asarray([vfill], np.int32))
            else:
                # nothing retained: whole new range conservative
                out_k.append(lo[:, None])
                out_v.append(np.asarray([vfill], np.int32))
            kcat = np.concatenate(out_k, axis=1)
            vcat = np.concatenate(out_v)
            if kcat.shape[1] > K:
                # cannot represent: collapse to fully conservative (safe)
                kcat = lo[:, None]
                vcat = np.asarray([vfill], np.int32)
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
        self.cut_bytes = list(new_cut_bytes)
        self._load_counts[:] = 0
        self._samples.clear()
        self.rebalances += 1
