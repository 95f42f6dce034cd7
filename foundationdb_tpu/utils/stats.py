"""Counters: per-role metric registries with periodic trace dumps.

Reference: flow/Stats.h:57-113 — Counter (value + rate tracking),
CounterCollection (a named bag of counters), and traceCounters (a periodic
TraceEvent with every counter's value and rate since the last dump).
"""

from __future__ import annotations

import os

from foundationdb_tpu.utils.trace import TraceEvent


class Counter:
    def __init__(self, name: str, collection: "CounterCollection" = None):
        self.name = name
        self.value = 0
        self._last_dumped = 0
        if collection is not None:
            collection.add(self)

    def __iadd__(self, n: int):
        self.value += n
        return self

    def increment(self, n: int = 1):
        self.value += n

    def set(self, v):
        """Gauge-style assignment (last-sampled value, not monotonic)."""
        self.value = v

    def rate_since_dump(self, dt: float) -> float:
        return (self.value - self._last_dumped) / dt if dt > 0 else 0.0


class CounterCollection:
    def __init__(self, name: str, ident: str = ""):
        self.name = name
        self.ident = ident
        self.counters: list[Counter] = []
        self._last_dump_time: float | None = None

    def add(self, counter: Counter):
        self.counters.append(counter)

    def counter(self, name: str) -> Counter:
        return Counter(name, self)

    def as_dict(self) -> dict:
        return {c.name: c.value for c in self.counters}

    def trace(self, now: float, event: str | None = None,
              extra: dict | None = None):
        """traceCounters (Stats.h:113): one event with values + rates."""
        ev = TraceEvent(event or f"{self.name}Metrics", self.ident)
        dt = (now - self._last_dump_time) if self._last_dump_time else 0.0
        for c in self.counters:
            ev.detail(c.name, c.value)
            if dt > 0:
                ev.detail(c.name + "Rate", round(c.rate_since_dump(dt), 2))
            c._last_dumped = c.value
        if extra:
            for k, v in extra.items():
                ev.detail(k, v)
        self._last_dump_time = now
        ev.log()


# Process-wide: stretches in which the real event loop did not tick (fed by
# net/transport.RealEventLoop's heartbeat, the reference's Net2 SlowTask).
process_metrics = CounterCollection("Process")
loop_stalls = process_metrics.counter("LoopStalls")
loop_stall_seconds = process_metrics.counter("LoopStallSeconds")
loop_stall_max_seconds = process_metrics.counter("LoopStallMaxSeconds")
# Full (generation 2) garbage collections, which hold the GIL: how many and
# how long in all (fed by utils/trace.span_full_collections, the process's
# one gc callback), and how many objects the heap policy of a server process
# froze before `ready` (net/server_main.settle_heap; 0 = no policy engaged).
full_collections = process_metrics.counter("FullCollections")
full_collection_seconds = process_metrics.counter("FullCollectionSeconds")
frozen_objects = process_metrics.counter("FrozenObjects")


def process_counters() -> dict:
    """The stall and collection counters and the CPU seconds (user + system)
    this process has used so far, read now."""
    t = os.times()
    return dict(process_metrics.as_dict(),
                ProcessCpuSeconds=round(t.user + t.system, 6))


def whole_process_counters(net) -> dict:
    """What belongs to the whole process and not to one role: the
    transport's counters (FramesIn/Out, BytesIn/Out, ChecksumRejects,
    NativeFastPathHits, PySlowPathFalls, ...) under a `Transport` prefix,
    and process_counters(). A sim network has no transport and its
    processes share one interpreter: nothing."""
    tc = getattr(net, "transport_counters", None)
    if tc is None:
        return {}
    return dict({"Transport" + k: v for k, v in tc().items()},
                **process_counters())


def fold_transport_counters(process, snap: dict) -> dict:
    """Merge whole_process_counters() into a role's metrics snapshot.
    Co-hosted roles report the same tallies — the rollup dedupes by process
    address. On a sim network the snapshot passes through."""
    snap.update(whole_process_counters(getattr(process, "net", None)))
    return snap


def trace_counters_loop(process, collection: CounterCollection,
                        interval: float = 5.0):
    """Spawnable actor: dump the collection every `interval` seconds.
    Real-network processes also carry whole_process_counters() in each dump
    (the same folding as the metrics RPC) so trace_analyze can roll up
    wire-plane activity, stalls and collections from the files alone."""
    async def loop():
        while True:
            await process.net.loop.delay(interval)
            collection.trace(process.net.loop.now(),
                             extra=whole_process_counters(process.net))
    return process.spawn(loop(), f"traceCounters/{collection.name}")
