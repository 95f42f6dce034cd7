"""Knob (configuration) bank.

Reference: flow/Knobs.cpp + fdbclient/Knobs.cpp + fdbserver/Knobs.cpp — a flat
registry of named numeric tunables, overridable at startup, where *the config
system doubles as a fault-injection surface*: under simulation with
buggification enabled, each knob may be randomly set to an extreme value
(`flow/Knobs.cpp:36` `init(..); if(randomize && BUGGIFY) ...` pattern).

We keep one bank. `Knobs.buggify(rng)` randomizes knobs that declare extreme
candidate values, using the deterministic RNG so runs stay replayable.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass, field
from typing import Any

from foundationdb_tpu.utils.rng import DeterministicRandom


@dataclass
class _Knob:
    name: str
    default: Any
    extremes: tuple = ()  # candidate buggified values


@dataclass
class Knobs:
    _defs: dict[str, _Knob] = field(default_factory=dict)
    _values: dict[str, Any] = field(default_factory=dict)

    def init(self, name: str, default: Any, extremes: tuple = ()):
        self._defs[name] = _Knob(name, default, extremes)
        self._values[name] = default

    def __getattr__(self, name: str):
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        raise AttributeError(name)

    def set(self, name: str, value: Any):
        if name not in self._defs:
            raise KeyError(f"unknown knob: {name}")
        self._values[name] = value

    def reset(self):
        for k, d in self._defs.items():
            self._values[k] = d.default

    def draw_buggified(self, rng, probability: float = 0.25) -> dict[str, Any]:
        """PURE draw of a buggified knob subset (deterministic under rng):
        which knobs would be randomized and to what, without applying them.
        The randomized harness records this draw in its repro line — the
        knob draw is part of the environment a failing seed must replay
        (SimulatedCluster's per-seed knob randomization, flow/Knobs.cpp
        BUGGIFY pattern)."""
        # One value from `rng`, then a stream of its own per knob, seeded
        # from that value and the knob's name: what a seed does to one knob
        # does not depend on which other knobs are registered. crc32 and not
        # hash(), which differs from process to process.
        base = rng.random_unique_id()
        drawn: dict[str, Any] = {}
        for k, d in sorted(self._defs.items()):
            if not d.extremes:
                continue
            own = DeterministicRandom((base << 32) | zlib.crc32(k.encode()))
            if own.random() < probability:
                drawn[k] = own.random_choice(d.extremes)
        return drawn

    def buggify(self, rng, probability: float = 0.25) -> dict[str, Any]:
        """Randomly set knobs that declare extremes (deterministic under
        rng). Returns the drawn subset {name: buggified_value}."""
        drawn = self.draw_buggified(rng, probability)
        self._values.update(drawn)
        return drawn

    def overrides(self, **kw):
        for k, v in kw.items():
            self.set(k, v)


KNOBS = Knobs()

# --- Versions / MVCC window (fdbserver/Knobs.cpp:30-34) ---
KNOBS.init("VERSIONS_PER_SECOND", 1_000_000)
KNOBS.init("MAX_READ_TRANSACTION_LIFE_VERSIONS", 5_000_000, (1_000_000,))
KNOBS.init("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 5_000_000, (1_000_000,))
KNOBS.init("MAX_VERSIONS_IN_FLIGHT", 100_000_000)
KNOBS.init("PROXY_MASTER_LEASE_SECONDS", 2.0)  # proxy GRV fencing lease
KNOBS.init("MASTER_CSTATE_LEASE_SECONDS", 2.0)  # master self-deposition lease

# --- Commit batching (fdbserver/Knobs.cpp:246-252, MasterProxyServer.actor.cpp:921) ---
KNOBS.init("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 32768, (1, 4))
# What closes a commit batch is server/proxy.py:_flush_due. INTERVAL_MIN is
# the linger: with no batch of the proxy's at the resolvers, a batch leaves
# once no commit has arrived for this long. INTERVAL_MAX is the cap on a
# batch's age whatever the resolvers are doing.
KNOBS.init("COMMIT_TRANSACTION_BATCH_INTERVAL_MIN", 0.001, (0.1,))
# INTERVAL_MAX sits deliberately ABOVE the time a saturated proxy takes to
# fill a BYTES_MIN batch (~23ms at the e2e write mix), so under heavy load
# the byte/count triggers — not the timer — govern batch size in every
# topology. A lower cap quietly re-fragments multi-proxy pools: each proxy
# fills bytes at 1/n the rate, hits the timer first, and the shared
# master/resolver/tlog core pays n-fold per-batch overhead (r10 measured
# 773 vs 435 batches for the same load with the old 0.010 cap).
KNOBS.init("COMMIT_TRANSACTION_BATCH_INTERVAL_MAX", 0.025)
KNOBS.init("COMMIT_TRANSACTION_BATCH_BYTES_MIN", 100_000)
KNOBS.init("COMMIT_BATCH_IDLE_INTERVAL", 0.25)  # empty-batch keepalive
# Bounded window of concurrent version batches in the proxy commit pipeline:
# resolve(N+1) overlaps tlog-push(N); 1 restores the serial pre-pipeline shape.
KNOBS.init("COMMIT_PIPELINE_DEPTH", 4, (1,))

# --- Conflict engine (device) ---
KNOBS.init("CONFLICT_BACKEND", "device")  # "device" (JAX) | "sharded" (mesh) | "oracle" (CPU reference)
# Mesh width for CONFLICT_BACKEND=sharded: how many devices the resolver's
# key-partitioned engine spans. 0 = every attached device (the production
# setting on a full slice); validated at worker boot like STORAGE_ENGINE
# and against the attached device count at engine construction.
KNOBS.init("CONFLICT_NUM_SHARDS", 0, (1, 2))
# resolutionBalancing analogue (masterserver.actor.cpp:955-1012): the sharded
# engine re-cuts its key partition at quantiles of sampled whole begin keys
# when per-shard load skews. Looked at every N steps (and at every step while
# a shard nears its capacity); moves when the busiest shard is offered
# > SKEW x the mean of the ranges counted since the last look, of which there
# have to be MIN_SAMPLES. 1.25 on four shards is 31% for one shard: an even
# partition reads 26-27% on its busiest shard by counting noise alone.
KNOBS.init("RESOLUTION_BALANCE_CHECK_BATCHES", 64, (4,))
KNOBS.init("RESOLUTION_BALANCE_SKEW", 1.25)
KNOBS.init("RESOLUTION_BALANCE_MIN_SAMPLES", 2048, (32,))
# Cross-epoch cut rebalancing: the resolver role feeds its HotRangeSketch
# (per-range decayed conflict mass) into the sharded engine every EPOCH
# seconds — conflict-mass-driven cuts on top of the load-sample path above.
KNOBS.init("RESOLUTION_BALANCE_EPOCH_SECONDS", 5.0, (0.5,))
KNOBS.init("CONFLICT_STATE_CAPACITY", 1 << 16, (1 << 10,))  # boundary slots
KNOBS.init("CONFLICT_BATCH_TXNS", 1024)  # static batch shape: txns
KNOBS.init("CONFLICT_BATCH_READS_PER_TXN", 4)
KNOBS.init("CONFLICT_BATCH_WRITES_PER_TXN", 4)
# Sandwich sweep rounds for the intra-batch evaluator; 0 = auto
# (min(txns // 2 + 1, 32) — guaranteed-exact for txns <= 64, bounded with a
# host-exact fallback beyond that; see conflict.py _run_sandwich).
KNOBS.init("CONFLICT_INTRA_ROUNDS", 0, (1,))
# What the device/sharded backend serves with when bound_device_discovery()
# finds NO accelerator (probe timeout / JAX_PLATFORMS=cpu): "host" = the
# exact host evaluator (ops/conflict_oracle.py, the semantic authority —
# XLA-on-CPU pays ~10-20x the per-txn cost of the host skiplist, so running
# the device kernel there loses end-to-end; see docs/conflict_kernel.md);
# "jax" = run the JAX kernel on the XLA CPU backend anyway (kernel CI,
# parity fuzz, measurement runs).
KNOBS.init("CONFLICT_CPU_FALLBACK", "host", ("jax",))

# --- Client (fdbclient/Knobs.cpp) ---
KNOBS.init("MAX_BATCH_SIZE", 20, (1,))  # read-version batcher
KNOBS.init("GRV_BATCH_INTERVAL", 0.0005, (0.01,))
KNOBS.init("READ_BATCH_INTERVAL", 0.0005, (0.01,))  # point-read batcher
KNOBS.init("READ_BATCH_MAX", 250, (2,))  # smaller batches pipeline better
KNOBS.init("DEFAULT_BACKOFF", 0.01, (1.0,))
# load balance (fdbrpc/LoadBalance.actor.h:159 + QueueModel): replicas are
# ordered by smoothed latency, and a duplicate "backup request" goes to the
# next-best replica once the first has been in flight MULT x its expected
# latency (floored) — the tail-latency hedge for one slow/clogged replica
KNOBS.init("LOAD_BALANCE_EWMA_ALPHA", 0.2)
KNOBS.init("LOAD_BALANCE_BACKUP_MULT", 5.0, (1.0,))
KNOBS.init("LOAD_BALANCE_MIN_BACKUP_DELAY", 0.005, (0.0005,))
KNOBS.init("MAX_BACKOFF", 1.0)
# Client-side commit admission control: AIMD budget on in-flight commits per
# Database, so clients stop stuffing the proxy queue they are measuring.
# Decrease fires on transaction_throttled and on commit latency inflating
# past LATENCY_RATIO x the level commits have been running at.
KNOBS.init("CLIENT_COMMIT_MAX_IN_FLIGHT", 256)
KNOBS.init("CLIENT_COMMIT_INITIAL_IN_FLIGHT", 32, (1,))
KNOBS.init("CLIENT_ADMISSION_LATENCY_RATIO", 6.0)
KNOBS.init("CLIENT_ADMISSION_DECREASE", 0.7)  # multiplicative cut factor
KNOBS.init("KEY_SIZE_LIMIT", 10_000)
KNOBS.init("VALUE_SIZE_LIMIT", 100_000)
KNOBS.init("TRANSACTION_SIZE_LIMIT", 10_000_000)

# --- Transport / simulation (flow/Knobs.cpp:51-52, fdbrpc/sim2.actor.cpp) ---
KNOBS.init("CONNECTION_MONITOR_TIMEOUT", 2.0, (0.1,))
# a real event loop whose heartbeat is this late is being held: its thread's
# stack is sampled and a SlowTask event logged (Net2's SLOWTASK_PROFILING)
KNOBS.init("SLOW_TASK_THRESHOLD", 0.25)
KNOBS.init("SIM_RPC_TIMEOUT_SECONDS", 5.0)  # dropped-packet visibility bound
KNOBS.init("SIM_MIN_LATENCY", 0.0001)
KNOBS.init("SIM_MAX_LATENCY", 0.002, (0.05,))
KNOBS.init("SIM_CLOG_PROBABILITY", 0.0)
KNOBS.init("BUGGIFY_ENABLED", False)

# --- TLog / storage ---
KNOBS.init("TLOG_QUORUM_ANTIQUORUM", 0)
KNOBS.init("TLOG_PEEK_REPLY_BYTES", 150_000, (10_000,))  # bounded peek pages
KNOBS.init("TLOG_SPILL_BYTES", 1_500_000, (100_000,))  # in-memory cap per log
# log-router pull-ahead bound, in versions past the slowest consumer's pop
# (LogRouter.actor.cpp bounds by bytes via LOG_ROUTER_MAX_SEARCH_MEMORY)
KNOBS.init("LOG_ROUTER_BUFFER_VERSIONS", 50_000_000)

# --- Ratekeeper (fdbserver/Ratekeeper.actor.cpp updateRate :250) ---
KNOBS.init("RK_UPDATE_INTERVAL", 0.5)
KNOBS.init("RK_TARGET_STORAGE_LAG_VERSIONS", 10_000_000)  # worst durability lag
KNOBS.init("RK_TARGET_TLOG_BYTES", 2_000_000, (200_000,))  # worst log queue
KNOBS.init("RK_BASE_TPS", 100_000.0)  # unthrottled budget
KNOBS.init("RK_SMOOTHING", 0.5)  # exponential smoothing per update

# --- Contention management (Ratekeeper.actor.cpp tag throttling +
# DataDistributionTracker read-hot-shard detection, re-aimed at write
# conflicts; see docs/contention.md) ---
KNOBS.init("CONTENTION_THROTTLE_ENABLED", True)
KNOBS.init("HOTSPOT_HALF_LIFE", 2.0)  # sketch decay half-life, seconds
KNOBS.init("HOTSPOT_MAX_BUCKETS", 256, (16,))  # sketch size bound
KNOBS.init("HOTSPOT_TOP_K", 8)  # ranges per RESOLVER_HOT_RANGES snapshot
# a range whose decayed conflict rate exceeds this is throttled
KNOBS.init("RK_THROTTLE_CONFLICT_RATE", 25.0, (2.0,))
# commits/sec the WHOLE proxy fleet may release into a throttled range
KNOBS.init("RK_THROTTLE_RELEASE_TPS", 50.0)
KNOBS.init("RK_THROTTLE_BACKOFF", 0.25)  # server-advised client backoff, s
KNOBS.init("RK_THROTTLE_MAX_BACKOFF", 2.0)  # advised-backoff ceiling
# DD conflict-split trigger: sustained conflict rate on a shard splits it
# even when its byte count is small (the hot-shard half of shardSplitter)
KNOBS.init("DD_SHARD_SPLIT_CONFLICT_RATE", 50.0)
KNOBS.init("DD_HOT_SHARD_ROUNDS", 2)  # consecutive hot DD rounds before split

# --- Storage read cache (storageserver read-hot detection re-aimed at the
# serving path: a bounded version-tagged value cache over ranges the
# HotRangeSketch flags hot; see docs/architecture.md "Read scale-out") ---
KNOBS.init("READ_CACHE_ENABLED", True, (False,))
KNOBS.init("READ_CACHE_MAX_ENTRIES", 4096, (4,))  # bounded: FIFO eviction
# one read in SAMPLE is folded into the read-hotness sketch (per-batch
# stride sampling keeps the serve path O(1) per batch, not O(keys))
KNOBS.init("READ_CACHE_SAMPLE", 16, (1,))
KNOBS.init("READ_CACHE_TOP_K", 16)  # hot ranges eligible for caching
# a sampled range is hot when its decayed read rate (scaled back up by the
# sampling stride) exceeds this, in reads/sec
KNOBS.init("READ_CACHE_HOT_RATE", 50.0, (1.0,))
KNOBS.init("READ_CACHE_REFRESH", 0.5)  # hot-set recompute period, seconds
# storage replicas recruited per shard, every one serving reads (the CC's
# recruitment fans each shard's tag set across failure domains; clusters
# constructed with an explicit n_replicas override this default)
KNOBS.init("READ_REPLICAS", 1)

# --- Data distribution (fdbserver/DataDistributionTracker.actor.cpp) ---
KNOBS.init("CC_PREEMPT_INTERVAL_SECONDS", 5.0)  # betterMasterExists poll
KNOBS.init("STORAGE_ENGINE", "memory")  # "memory" | "ssd" | "redwood" (KeyValueStoreType)
KNOBS.init("SSD_DATA_DIR", "")  # "" -> the platform temp dir

# --- Redwood storage engine (storage/redwood.py; the reference's
# ssd-redwood-v1, VersionedBTree.actor.cpp knob family) ---
KNOBS.init("REDWOOD_MEMTABLE_BYTES", 4_000_000, (8_192,))  # flush trigger
KNOBS.init("REDWOOD_BLOCK_BYTES", 16_384, (512,))  # sorted-block target size
KNOBS.init("REDWOOD_COMPACTION_FAN_IN", 4, (2,))  # runs per level -> merge
KNOBS.init("REDWOOD_BLOCK_CACHE_BLOCKS", 1_024, (2,))  # decoded-block cache
KNOBS.init("REDWOOD_MAINT_INTERVAL", 0.25)  # storage-server poll period
# native read path (fdb_native.c RedwoodRun): 0 forces the pure-Python
# lookup even when the extension is importable — the parity-fuzz lever
KNOBS.init("REDWOOD_NATIVE_READS", 1, (0,))
KNOBS.init("REDWOOD_BLOOM_BITS_PER_KEY", 10, (0,))  # 0 -> no bloom section
KNOBS.init("REDWOOD_BLOOM_HASHES", 6)  # double-hashing probe count
KNOBS.init("DD_INTERVAL_SECONDS", 2.0)  # shard tracker poll period
# a storage worker silent for this long is treated as permanently failed and
# its shards are re-replicated onto a replacement (storageServerFailureTracker
# / DD_FAILURE_TIME; short here because sim time is cheap)
KNOBS.init("DD_STORAGE_FAILURE_SECONDS", 8.0, (2.0,))
KNOBS.init("DD_SHARD_SPLIT_BYTES", 500_000, (5_000,))  # shardSplitter :314 threshold
KNOBS.init("DD_SHARD_MERGE_BYTES", 50_000, (500,))  # shardMerger :379 threshold
KNOBS.init("STORAGE_DURABILITY_LAG_VERSIONS", 2_000_000)
KNOBS.init("DESIRED_TOTAL_BYTES", 150_000)  # range-read reply soft limit
# serve incoming connections through the C transport data plane
# (net/native_transport.py); NET_NATIVE_TRANSPORT=1 in the environment
# overrides. Not buggified: the sim never constructs a NetTransport.
KNOBS.init("NET_NATIVE_TRANSPORT", 0)
# client half of the data plane: batched C request encode + C reply pump
# (ClientConn) on outbound connections; NET_NATIVE_CLIENT=1 in the
# environment overrides. Same no-buggify rationale as above.
KNOBS.init("NET_NATIVE_CLIENT", 0)

# --- Ratekeeper (fdbserver/Ratekeeper.actor.cpp) ---
KNOBS.init("RATEKEEPER_DEFAULT_LIMIT", 1e9)
KNOBS.init("TARGET_BYTES_PER_STORAGE_SERVER", 1_000_000_000)

# --- Data distribution ---
KNOBS.init("SHARD_MAX_BYTES", 500_000_000, (10_000,))
KNOBS.init("SHARD_MIN_BYTES", 200_000, (1_000,))
