"""Resolver role: orders commit batches and runs the conflict engine.

Reference: fdbserver/Resolver.actor.cpp — resolveBatch (:71): batches from all
proxies are serialized per-resolver by waiting version.whenAtLeast(prevVersion)
(:104-115), the ConflictBatch decides each transaction (:140-157), duplicate
(retransmitted) batches get their cached reply (:117-128), and the reply
carries one status per transaction (:159-166).

The conflict engine is the knob-dispatched seam (ConflictSet.h:28): "device" =
the JAX/TPU batched kernel (ops/conflict.py), "oracle" = the pure-Python CPU
reference (ops/conflict_oracle.py). Both make identical decisions (tested).
"""

from __future__ import annotations

import os

from foundationdb_tpu.core.future import settle_failed
from foundationdb_tpu.core.notified import AsyncTrigger, NotifiedVersion
from foundationdb_tpu.core.sim import SimProcess
from foundationdb_tpu.ops.batch import validate_conflict_config
from foundationdb_tpu.ops.conflict import DeviceConflictSet
from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
from foundationdb_tpu.server.hotspot import HotRangesReply, HotRangeSketch
from foundationdb_tpu.server.interfaces import (
    ResolveTransactionBatchReply, ResolveTransactionBatchRequest, Token)
from foundationdb_tpu.utils.errors import FDBError
from foundationdb_tpu.utils.knobs import KNOBS
from foundationdb_tpu.utils.stats import CounterCollection, trace_counters_loop
from foundationdb_tpu.utils.trace import SevWarn, TraceEvent, g_trace_batch


def new_conflict_set(oldest_version: int = 0,
                     key_range: tuple[bytes, bytes | None] = (b"", None)):
    """newConflictSet() dispatch (ConflictSet.h:28) on the CONFLICT_BACKEND knob.

    "device"  — single-device JAX kernel
    "sharded" — key-partitioned SPMD engine over the device mesh
                (parallel/sharded_conflict.py): CONFLICT_NUM_SHARDS devices
                (0 = every attached device). Its cuts are whole keys. It
                starts on equal cuts of `key_range` and moves them itself
                (resolutionBalancing): to quantiles of the keys it sampled,
                when the ranges it counted per shard are skewed — looked at
                every RESOLUTION_BALANCE_CHECK_BATCHES steps, and at once
                while a shard nears its capacity — and to quantiles of the
                conflict mass this role's sketch hands it every
                RESOLUTION_BALANCE_EPOCH_SECONDS. A move shows as a
                `Resolver.Recut` section and in `CutRebalances`;
                `ShardRangesOffered`, `ShardRangesFullest` and
                `ShardBoundariesFullest` say how even the partition is
    "oracle"  — pure-Python CPU reference

    `key_range` is the resolver's OWNED range from the outer ResolverMap
    partition: in an n_resolvers > 1 topology the proxy's key split stays the
    outer cut while the sharded engine's mesh subdivides [begin, end) as the
    inner one, so the two compose instead of fighting over the keyspace.

    Device backends attach the accelerator here, in-process, once
    (utils/jaxenv.serving_platform). If JAX finds no accelerator, or
    attaching raises, this raises and the server process dies at boot with
    the reason on stderr. Only when JAX_PLATFORMS=cpu was set from outside
    (tier-1 tests, the simulator) does a device backend run on the CPU, and
    only there does CONFLICT_CPU_FALLBACK pick its evaluator.
    """
    validate_conflict_config()
    if KNOBS.CONFLICT_BACKEND in ("device", "sharded"):
        from foundationdb_tpu.utils.jaxenv import serving_platform
        backend_label = serving_platform()
        if backend_label == "cpu" and KNOBS.CONFLICT_CPU_FALLBACK == "host":
            # The operator asked for the CPU: the XLA-on-CPU step costs
            # ~10-20x the host skiplist per txn (one core runs BOTH the
            # engine and the whole pipeline), so degrade the *evaluator* to
            # the exact host path while keeping the backend knob's serving
            # contract. Decisions are identical by construction (the oracle
            # is the semantic authority the device kernel is fuzzed against).
            cs = OracleConflictSet(oldest_version=oldest_version)
            cs.backend_label = f"{backend_label}+host-evaluator"
            return cs
    if KNOBS.CONFLICT_BACKEND == "device":
        cs = DeviceConflictSet(oldest_version=oldest_version)
        cs.backend_label = backend_label
        return cs
    if KNOBS.CONFLICT_BACKEND == "sharded":
        import jax

        from foundationdb_tpu.parallel.sharded_conflict import (
            ShardedDeviceConflictSet, make_resolver_mesh,
            shard_cut_bytes_range)
        n = int(KNOBS.CONFLICT_NUM_SHARDS)
        avail = len(jax.devices())
        if n > avail:
            raise FDBError(
                "invalid_option",
                f"CONFLICT_NUM_SHARDS={n} exceeds the {avail} attached "
                f"device(s); set 0 to span all of them")
        mesh = make_resolver_mesh(n or None)
        cuts = shard_cut_bytes_range(mesh.devices.size,
                                     key_range[0], key_range[1])
        cs = ShardedDeviceConflictSet(mesh=mesh,
                                      oldest_version=oldest_version,
                                      cut_bytes=cuts)
        cs.backend_label = f"{backend_label}x{mesh.devices.size}"
        return cs
    return OracleConflictSet(oldest_version=oldest_version)


class Resolver:
    def __init__(self, process: SimProcess, recovery_version: int = 0,
                 n_proxies: int = 1, key_range_begin: bytes = b"",
                 key_range_end: bytes | None = None):
        self.process = process
        self.n_proxies = n_proxies
        # this resolver's slice of the outer ResolverMap partition; the
        # sharded engine's mesh cuts subdivide it (inner split)
        self.key_range = (key_range_begin, key_range_end)
        self.version = NotifiedVersion(recovery_version)
        self.conflict_set = new_conflict_set(oldest_version=recovery_version,
                                             key_range=self.key_range)
        self._pipelined = hasattr(self.conflict_set, "detect_async")
        if self._pipelined:
            # Force the device programs (all serving buckets) to compile
            # NOW: a cold-cache XLA compile on the first SERVED commit would
            # stall the pipeline for tens of seconds. Subsequent
            # constructions (recoveries) hit the in-process jit cache;
            # cross-process runs hit the persistent compile cache.
            import time
            t0 = time.perf_counter()
            self.conflict_set.warmup()
            self._warmup_seconds = round(time.perf_counter() - t0, 3)
            # the engine's own sections (Resolver.Encode/Enqueue/Readback/
            # Collect) stamp this loop's time: virtual under the simulator
            self.conflict_set.trace_clock = process.net.loop.now
            # a server that writes span files also says which scope each
            # instruction of its step programs runs under (a profile of the
            # chip names operations by instruction alone)
            trace_dir = os.environ.get("FDBTPU_TRACE_DIR")
            if trace_dir and hasattr(self.conflict_set, "write_scope_maps"):
                self.conflict_set.write_scope_maps(trace_dir)
        self._recent_replies: dict[int, ResolveTransactionBatchReply] = {}
        # retained state (metadata) transactions for other proxies' catch-up
        # (Resolver.actor.cpp:59-62,170-224): version -> [(locally_committed,
        # mutations)], pruned below the oldest proxy's received version
        self._recent_state_txns: dict[int, list] = {}
        self._proxy_last: dict[int, int] = {}  # proxy_id -> last version
        self.total_resolved = 0
        # Device pipelining: dispatched-but-unread batches in version order.
        # The readback drains in GROUPS with one device sync per drain
        # (ops/conflict.drain_handles), off the loop thread, so resolver
        # throughput is set by dispatch rate while GRV/reads keep flowing —
        # the serving-path analogue of the proxy's phase pipelining
        # (MasterProxyServer.actor.cpp:364-366).
        self._drain_pending: list = []
        self._drain_wake = AsyncTrigger()
        self._drained_seq = NotifiedVersion(0)  # drain-group ordering gate
        self._drain_groups: set = set()  # in-flight readback actors
        # set when the device state overflowed (truncated state could yield
        # FALSE COMMITS): this resolver must stop deciding batches — every
        # reply is an error until a recovery replaces it with a fresh
        # conflict set (clearConflictSet semantics, SkipList.cpp:957)
        self._poisoned: FDBError | None = None
        self._drain_task = (process.spawn(self._drain_loop(), "resolverDrain")
                            if self._pipelined else None)
        self.counters = CounterCollection("Resolver", str(process.address))
        self._c_batches = self.counters.counter("BatchesIn")
        self._c_txns = self.counters.counter("TxnResolved")
        self._c_groups = self.counters.counter("DrainGroups")
        # the device state's fill and churn, one sample a kernel step, read
        # off the scalars that ride each step's verdicts (DetectHandle.steps):
        # boundaries held after the step and the capacity they are held in
        # (so a mean fill needs no constant), the fullest step so far, and
        # the rows the step's window GC dropped
        self._c_state_boundaries = self.counters.counter("StateBoundariesSum")
        self._c_state_capacity = self.counters.counter("StateCapacitySum")
        self._c_state_peak = self.counters.counter("StateBoundariesPeak")
        self._c_state_evicted = self.counters.counter("StateEvictedSum")
        self._state_near_full = False
        # conflict-hotspot detection (docs/contention.md): every rejected
        # txn's write ranges feed the decayed sketch; ratekeeper and DD poll
        # the snapshot via RESOLVER_HOT_RANGES
        self.hot_sketch = HotRangeSketch()
        self._c_sampled = self.counters.counter("ConflictsSampled")
        # cross-epoch cut rebalancing (sharded engine only): the sketch's
        # decayed per-range conflict mass drives the inner-mesh recut
        self._balance_task = (
            process.spawn(self._balance_loop(), "resolverBalance")
            if hasattr(self.conflict_set, "rebalance_from_conflicts")
            else None)
        process.register(Token.RESOLVER_RESOLVE, self._on_resolve)
        process.register(Token.RESOLVER_METRICS, self._on_metrics)
        process.register(Token.RESOLVER_HOT_RANGES, self._on_hot_ranges)
        self._counters_task = trace_counters_loop(process, self.counters)

    def shutdown(self):
        """Displaced by a re-created resolver on the same worker."""
        self._counters_task.cancel()
        if self._drain_task is not None:
            self._drain_task.cancel()
        if self._balance_task is not None:
            self._balance_task.cancel()
        for t in list(self._drain_groups):
            t.cancel()

    def _on_metrics(self, req, reply):
        """Role counters + the process-wide device gauges (transfer bytes,
        kernel dispatches, readback wait, compile cache) the reference never
        needed — a resolver is the only role that drives the device."""
        from foundationdb_tpu.ops import conflict
        from foundationdb_tpu.utils import jaxenv
        snap = self.counters.as_dict()
        snap["Version"] = self.version.get()
        snap["Backend"] = getattr(self.conflict_set, "backend_label", "oracle")
        snap["Poisoned"] = self._poisoned is not None
        if self._pipelined:
            snap.update(jaxenv.device_identity())
            snap["WarmupSeconds"] = self._warmup_seconds
        cs = self.conflict_set
        if hasattr(cs, "rebalance_from_conflicts"):
            # the partition (parallel/sharded_conflict.py): every applied
            # move of the cuts whatever planned it, the ranges that clipped
            # non-empty summed over shards, a step's count on its busiest
            # shard summed over steps, the fullest shard's boundaries
            snap["CutRebalances"] = cs.rebalances
            snap["ShardRangesOffered"] = cs.ranges_offered
            snap["ShardRangesFullest"] = cs.ranges_fullest
            snap["ShardBoundariesFullest"] = cs.fill_fullest
        snap.update(conflict.kernel_metrics.as_dict())
        snap.update(conflict.compile_cache_stats())
        snap.update(jaxenv.transfer_metrics.as_dict())
        snap["HotRangeBuckets"] = len(self.hot_sketch)
        snap["HotRangeTotalRate"] = round(
            self.hot_sketch.total_rate(self.process.net.loop.now()), 3)
        from foundationdb_tpu.utils.stats import fold_transport_counters
        reply.send(fold_transport_counters(self.process, snap))

    def _on_hot_ranges(self, req, reply):
        """Conflict-hotspot snapshot (ratekeeper + DD poll): hottest K
        ranges by decayed conflict rate, deterministically ordered."""
        k = req if isinstance(req, int) and req > 0 else KNOBS.HOTSPOT_TOP_K
        now = self.process.net.loop.now()
        self.hot_sketch.prune(now)
        reply.send(HotRangesReply(ranges=self.hot_sketch.top_k(k, now),
                                  total_rate=self.hot_sketch.total_rate(now)))

    def _on_resolve(self, req: ResolveTransactionBatchRequest, reply):
        self.process.spawn(self._resolve_batch(req, reply), "resolveBatch")

    async def _resolve_batch(self, req: ResolveTransactionBatchRequest, reply):
        try:
            await self.version.when_at_least(req.prev_version)
        except FDBError as e:
            # displaced/cancelled while parked on the version gate: settle
            # before dying, or the proxy waits out the full RPC timeout
            # (protolint PROTO002)
            settle_failed(reply, e)
            raise
        if self._poisoned is not None:
            # the batch still takes its place in the order, so that the ones
            # chained behind it are answered too and not left at the gate
            if req.version > self.version.get():
                self.version.set(req.version)
            reply.send_error(self._poisoned)
            return
        if req.version <= self.version.get():
            cached = self._recent_replies.get(req.version)
            if cached is not None:
                reply.send(cached)
            # unknown old version: a retransmit from before our recovery —
            # drop (the reply may still be draining); the proxy retries and
            # finds the cached reply once the drain lands
            return  # protolint: ignore[PROTO002] — deliberate drop, see above
        cs = self.conflict_set
        self._c_batches.increment()
        loop = self.process.net.loop
        vid = f"v{req.version}"
        if self._pipelined:
            # Enqueue transfer+compute now — device state is updated at
            # dispatch in version order, so the NEXT batch may dispatch as
            # soon as version advances; the verdict readback happens in the
            # drain loop without ever blocking dispatch.
            t_dispatch = loop.now()
            with g_trace_batch.section("CommitSpan", vid, "Resolver.Dispatch",
                                       now=loop.now):
                handle = cs.detect_async(req.transactions, req.version)
            self.version.set(req.version)
            self._drain_pending.append(
                (req, reply, handle, loop.now() - t_dispatch))
            self._drain_wake.trigger()
            return
        with g_trace_batch.section("CommitSpan", vid, "Resolver.Dispatch",
                                   now=loop.now):
            statuses = cs.detect(req.transactions, req.version)
        self.version.set(req.version)
        self._finish_batch(req, reply, statuses)

    async def _drain_loop(self):
        """Group dispatched batches and spawn one overlapped readback actor
        per group: group k+1's device→host copies fly while group k's are
        still in flight (readbacks overlap on the wire), and the sequence
        gate keeps _finish_batch strictly in dispatch order."""
        seq = 0
        while True:
            if not self._drain_pending:
                await self._drain_wake.on_trigger()
                continue
            entries, self._drain_pending = self._drain_pending, []
            seq += 1
            t = self.process.spawn(self._drain_group(seq, entries),
                                   f"resolverDrain{seq}")
            self._drain_groups.add(t)
            t.add_system_callback(lambda _f, t=t: self._drain_groups.discard(t))

    async def _drain_group(self, seq: int, entries: list):
        from foundationdb_tpu.ops.conflict import drain_and_collect
        loop = self.process.net.loop
        handles = [h for _req, _reply, h, _d in entries]
        err = None
        results: list | None = None
        sharded = hasattr(self.conflict_set, "rebalance_from_conflicts")
        self._c_groups.increment()
        try:
            try:
                # drain AND materialize off-loop: result() can run the exact
                # host intra-batch fallback on an unconverged chunk, which
                # must not eat event-loop time (devlint DEV001)
                timing: dict = {}
                t_rb0 = loop.now()
                results = await loop.run_blocking(
                    lambda hs=handles: drain_and_collect(hs, timing))
                # per-entry readback spans, emitted only once the wait
                # completed (a cancel mid-drain must not leave open spans);
                # all entries in a group share one device sync, so they
                # share its window. On the sharded backend the window is
                # split: the device sync is ReadbackWait, the host
                # materialization of the pmin-combined verdicts is
                # ShardCombine (single-device unpack is negligible and
                # stays inside ReadbackWait).
                t_rb1 = loop.now()
                t_split = t_rb1
                if sharded:
                    wall = (timing.get("drain_seconds", 0.0)
                            + timing.get("collect_seconds", 0.0))
                    if wall > 0.0:
                        t_split = t_rb0 + (t_rb1 - t_rb0) * (
                            timing["drain_seconds"] / wall)
                for req, _reply, _h, _d in entries:
                    vid = f"v{req.version}"
                    g_trace_batch.span_begin("CommitSpan", vid,
                                             "Resolver.ReadbackWait", at=t_rb0)
                    g_trace_batch.span_end("CommitSpan", vid,
                                           "Resolver.ReadbackWait", at=t_split)
                    if sharded:
                        g_trace_batch.span_begin("CommitSpan", vid,
                                                 "Resolver.ShardCombine",
                                                 at=t_split)
                        g_trace_batch.span_end("CommitSpan", vid,
                                               "Resolver.ShardCombine",
                                               at=t_rb1)
            except FDBError as e:
                if e.name == "operation_cancelled":
                    raise  # killed/displaced mid-drain: die, don't reply
                err = e
            except BaseException as e:  # noqa: BLE001 — fail the whole group
                err = FDBError("internal_error", str(e))
            await self._drained_seq.when_at_least(seq - 1)
            if results is None:
                results = [(None, None)] * len(entries)
            for (req, reply, handle, dispatch_s), (statuses, herr) in zip(
                    entries, results):
                if err is None and herr is not None:
                    err = herr  # state overflow: fatal
                if err is not None:
                    # a truncated state can yield FALSE COMMITS: poison the
                    # resolver so every later (already-dispatched or new)
                    # batch errors too; the proxy's pipeline failure then
                    # drives a recovery that builds a fresh conflict set
                    if self._poisoned is None:
                        TraceEvent("ResolverPoisoned", self.process.address) \
                            .detail("Version", req.version).error(err).log()
                    self._poisoned = err
                    reply.send_error(err)
                    continue
                self._note_state(handle.steps, req.version)
                self._finish_batch(req, reply, statuses, dispatch_s)
        finally:
            # The finally covers BOTH awaits: a cancel landing in
            # run_blocking or in the ordering wait must still advance the
            # sequencing gate, or every later drain group wedges forever on
            # when_at_least(seq - 1) (round-5 ADVICE, resolver.py:148).
            self._advance_drained(seq)

    def _note_state(self, steps: list[tuple[int, int]], version: int):
        """Count what each step of a batch left in the device state; say so
        once when a step leaves it above 7/8 of its capacity (an overflow
        poisons this resolver), and again only after it has been below."""
        capacity = self.conflict_set.shapes.capacity
        for boundaries, evicted in steps:
            self._c_state_boundaries.increment(boundaries)
            self._c_state_capacity.increment(capacity)
            self._c_state_evicted.increment(evicted)
            if boundaries > self._c_state_peak.value:
                self._c_state_peak.set(boundaries)
            near_full = 8 * boundaries > 7 * capacity
            if near_full and not self._state_near_full:
                TraceEvent("ResolverStateNearFull", self.process.address,
                           severity=SevWarn) \
                    .detail("Version", version) \
                    .detail("Boundaries", boundaries) \
                    .detail("Capacity", capacity).log()
            self._state_near_full = near_full

    async def _balance_loop(self):
        """Cross-epoch cut rebalancing — the resolutionBalancing analogue
        (masterserver.actor.cpp:955-1012) driven by CONFLICT mass instead of
        raw iops: every RESOLUTION_BALANCE_EPOCH_SECONDS the decayed
        per-range conflict rates from the hotspot sketch feed the sharded
        engine's cut planner. The planner only computes and SCHEDULES new
        cuts (pure host numpy — no device sync on the loop thread, devlint
        DEV001); the engine applies the state restructure at its next
        dispatch, so cuts never move under an in-flight batch."""
        loop = self.process.net.loop
        while True:
            await loop.delay(KNOBS.RESOLUTION_BALANCE_EPOCH_SECONDS)
            now = loop.now()
            self.hot_sketch.prune(now)
            hot = self.hot_sketch.top_k(KNOBS.HOTSPOT_MAX_BUCKETS, now)
            if not hot:
                continue
            self.conflict_set.rebalance_from_conflicts(
                [(r.begin, r.end, r.rate) for r in hot])

    def _advance_drained(self, seq: int):
        """Advance the drain-ordering gate to `seq` without ever moving it
        backwards or jumping over a still-running predecessor group: if the
        gate hasn't reached seq - 1 yet, chain the advance off the
        predecessor's settle instead of setting out of order."""
        def advance(_f=None):
            if self._drained_seq.get() < seq:
                self._drained_seq.set(seq)
        self._drained_seq.when_at_least(seq - 1).add_callback(advance)

    def _finish_batch(self, req: ResolveTransactionBatchRequest, reply,
                      statuses: list[int], dispatch_s: float = 0.0):
        """Statuses-dependent bookkeeping + reply, strictly in version order
        (drain preserves dispatch order, so batch N's state txns are always
        recorded before batch N+1 assembles its catch-up window)."""
        self.total_resolved += len(req.transactions)
        self._c_txns.increment(len(req.transactions))

        # hotspot detection: fold each REJECTED txn's write ranges into the
        # decayed sketch at the sim-time of the verdict (deterministic)
        from foundationdb_tpu.ops.batch import CONFLICT
        now = self.process.net.loop.now()
        sampled = 0
        for txn, status in zip(req.transactions, statuses):
            if status == CONFLICT and txn.write_ranges:
                self.hot_sketch.record(txn.write_ranges, now)
                sampled += 1
        if sampled:
            self._c_sampled.increment(sampled)

        # record this batch's state txns with the LOCAL verdict; proxies AND
        # verdicts across resolvers for the global one (:452-459 in the proxy)
        from foundationdb_tpu.ops.batch import COMMITTED
        if req.state_txn_indices:
            muts = req.state_txn_mutations or [[]] * len(req.state_txn_indices)
            self._recent_state_txns[req.version] = [
                (statuses[i] == COMMITTED, m)
                for i, m in zip(req.state_txn_indices, muts)]
        # hand back state txns from versions this proxy hasn't seen
        state_out = [(v, entries)
                     for v, entries in sorted(self._recent_state_txns.items())
                     if req.last_receive_version < v < req.version]
        r = ResolveTransactionBatchReply(committed=statuses,
                                         state_mutations=state_out,
                                         dispatch_s=dispatch_s)
        self._recent_replies[req.version] = r
        # prune: state txns below every proxy's received version; replies
        # outside the MVCC window (reference prunes by oldestProxyVersion,
        # Resolver.actor.cpp:198-224)
        # prune by what proxies have ACKED receiving (last_receive_version =
        # the proxy applied windows through its previous batch), not by what
        # was merely sent to them: a proxy that lost this reply can then
        # rewind and re-fetch its window instead of losing it to pruning.
        # (The reference prunes by lastVersion and instead kills any proxy
        # that misses a reply; ack-based pruning is strictly safer.)
        self._proxy_last[req.proxy_id] = max(
            self._proxy_last.get(req.proxy_id, 0), req.last_receive_version)
        if len(self._proxy_last) >= self.n_proxies:
            # only once every proxy has reported (the reference's
            # proxyInfoMap.size() == proxyCount guard): pruning earlier would
            # drop state txns an unheard-from proxy still needs
            oldest_proxy = min(self._proxy_last.values())
            for v in [v for v in self._recent_state_txns if v <= oldest_proxy]:
                del self._recent_state_txns[v]
        floor = req.version - KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS
        for v in [v for v in self._recent_replies if v < floor]:
            del self._recent_replies[v]
        reply.send(r)
