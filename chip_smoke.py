"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Default (one chip): boots the real process cluster through
foundationdb_tpu.net.server_main with CONFLICT_BACKEND=device (one core
process holding the chip: master + resolver + tlog + commit proxy; two storage
processes), loads PAIRS key-value pairs through the ordinary client API, runs
a few hundred read-modify-write transactions the conflict kernel must judge
(including pairs built to conflict and pairs built not to), reads every
acknowledged write back from storage against a plain dict model, and fetches
the resolver's metrics over the wire as evidence that the chip decided every
batch. After every server child is reaped, phase `kernel` attaches the chip
in THIS process and replays a seeded stream of conflict batches through
DeviceConflictSet and the independent OracleConflictSet at the served shapes.

`--config <file>` takes the key count, the kernel's shapes and the loader's
shape from a benchmark configuration (benchmark/configs/*.json, 16-byte
decimal keys) instead of the defaults below: the same run at that
deployment's scale.

`--chips 4` runs only the mesh-sharded engine against per-shard oracles, in
one process, over four real devices: the benchmark's keys at the served shape
from a cold start, with two moves of the cuts to whole keys in mid-stream.

One process per chip: until the last child is reaped this parent imports the
client stack only and never JAX. Any failed phase exits non-zero at once;
a missing accelerator is an error, never a fallback. One JSON object per
phase on stdout; the last line is {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Upstream's benchmark shapes (BASELINE.md, benchmarking.rst): 16-byte keys,
# 8-100 byte values, 10 operations per transaction.
PAIRS = 100_000
OPS_PER_TXN = 10
LOADERS = 64
LOAD_SETS_PER_TXN = OPS_PER_TXN
ACTORS = 16
RMW_PER_ACTOR = 10
BUILT_PAIRS_PER_ACTOR = 4  # of each kind: conflicting and disjoint
# The bucket shape the core serves with (bench_e2e's device shape at a
# capacity that holds the load: a distinct point write costs two boundaries
# for the 5 s MVCC window, PAIRS * 2 < 2^18). Four bucket programs compile
# in ~240 s cold (PERF.md "compile times"); the boot deadline leaves them
# three times that.
SERVED_KNOBS = {
    "CONFLICT_STATE_CAPACITY": 1 << 18,
    "CONFLICT_BATCH_TXNS": 256,
    "CONFLICT_BATCH_READS_PER_TXN": 10,
    "CONFLICT_BATCH_WRITES_PER_TXN": 10,
}
BOOT_DEADLINE_SECONDS = 900
PLATFORM = "tpu"  # what the core and this process must report
# --chips 4: the sharded engine runs the full T//2+1 sandwich rounds in one
# program (no buckets). The served bucket shape, capacity 2^16 per shard,
# compiles for the four-chip mesh in ~36 s (PERF.md "compile times").
SHARDED_SHAPE = {"capacity": 1 << 16, "txns": 256, "reads_per_txn": 10,
                 "writes_per_txn": 10}


def use_config(path: str) -> None:
    """Serve and replay at the scale a benchmark configuration states: its
    record count, its conflict knobs and its loader's shape."""
    global PAIRS, LOADERS, LOAD_SETS_PER_TXN
    with open(path) as f:
        config = json.load(f)
    key = config["data"]["key"]
    check(key == {"kind": "decimal", "bytes": 16},
          f"{path}: this script writes 16-byte decimal keys, not {key}")
    PAIRS = int(config["data"]["records"])
    SERVED_KNOBS.update({k: config["knobs"][k] for k in SERVED_KNOBS})
    LOADERS = int(config["load"]["loaders"])
    LOAD_SETS_PER_TXN = int(config["load"]["sets_per_txn"])


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(msg: str) -> None:
    sys.stderr.write(f"chip_smoke: FAILED: {msg}\n")
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# what the core's RESOLVER_METRICS snapshot is quoted for, under its own names
BOOT_EVIDENCE = ("Backend", "Platform", "DeviceKind", "DeviceCount",
                 "WarmupSeconds", "CompileCacheHits", "CompileCacheMisses",
                 "PersistentCacheHits", "PersistentCacheMisses")
SERVED_EVIDENCE = ("Backend", "Platform", "DeviceKind", "DeviceCount",
                   "Poisoned", "BatchesIn", "TxnResolved", "KernelDispatches",
                   "HostExactChunks", "CompileCacheHits", "CompileCacheMisses",
                   "PersistentCacheHits", "PersistentCacheMisses",
                   "DevicePutBytes", "DeviceGetBytes", "ReadbackWaitSeconds",
                   "StateBoundariesSum", "StateCapacitySum",
                   "StateBoundariesPeak", "StateEvictedSum")


KERNEL_EVIDENCE = ("KernelDispatches", "HostExactChunks",
                   "PersistentCacheHits", "PersistentCacheMisses")


def device_line(ident: dict) -> dict:
    """jaxenv.device_identity() / RESOLVER_METRICS -> the last line's keys."""
    return {"platform": ident["Platform"], "kind": ident["DeviceKind"],
            "count": ident["DeviceCount"]}


def assert_off_jax() -> None:
    check("jax" not in sys.modules,
          "the parent imported jax while server children may hold the chip")


def key_of(i: int) -> bytes:
    return b"%016d" % i


def bump(value: bytes) -> bytes:
    """The read-modify-write: the first 8 bytes are a counter."""
    n = int.from_bytes(value[:8], "big") + 1
    return n.to_bytes(8, "big") + value[8:]


# ------------------------------------------------------------ phase: native

def phase_native() -> None:
    """Rebuild the C extension from the committed source ONCE, before any
    child imports the package (each would otherwise build it, at once, into
    the one path)."""
    so = os.path.join(HERE, "foundationdb_tpu", "native", "fdb_native.so")
    if os.path.exists(so):
        os.remove(so)
    t0 = time.monotonic()
    from foundationdb_tpu import native
    check(native.available(),
          f"native extension did not build (no C compiler?): "
          f"{native.build_error()}")
    emit("native", available=native.available(),
         build_seconds=round(time.monotonic() - t0, 2))


# ------------------------------------------------------------ phase: served

def _tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError as e:
        return f"<{e}>"


def phase_served(seed: int, out_dir: str) -> dict:
    """Returns the device identity the core process reported."""
    import tempfile

    import bench_e2e
    from foundationdb_tpu.core.sim import Endpoint
    from foundationdb_tpu.net.transport import RealEventLoop
    from foundationdb_tpu.server.interfaces import Token
    from foundationdb_tpu.utils import trace
    from foundationdb_tpu.utils.errors import FDBError
    assert_off_jax()
    # spans and role counters go to files beside the servers' stderr, which
    # then holds only what a process had to say about failing
    trace_dir = os.path.join(out_dir, "traces")
    client_trace = trace.RollingTraceFile(
        os.path.join(trace_dir, "trace.client.jsonl"))
    trace.set_sink(client_trace.write)

    rng = random.Random(seed)
    model = {key_of(i): rng.randbytes(rng.randint(8, 100))
             for i in range(PAIRS)}
    tmp = tempfile.mkdtemp(prefix="fdbtpu-chip-smoke-")
    t0 = time.monotonic()
    try:
        procs, _labels, proxies, boundaries, teams, _grv = \
            bench_e2e._boot_cluster(
                tmp, "device", n_proxies=0, n_storage=2,
                extra_knobs=dict(SERVED_KNOBS),
                cut_keys=[key_of(PAIRS // 2)], trace_dir=trace_dir,
                boot_deadline=BOOT_DEADLINE_SECONDS, log_dir=out_dir)
    except (RuntimeError, TimeoutError) as e:
        sys.stderr.write(_tail(os.path.join(out_dir, "core.stderr")) + "\n")
        fail(f"cluster did not boot: {e}")
    boot_seconds = time.monotonic() - t0
    core = proxies[0]
    try:
        loop = RealEventLoop()
        client, db = bench_e2e._make_db(loop, proxies, boundaries, teams)

        async def resolver_metrics() -> dict:
            return dict(await loop.timeout(client.process.net.request(
                client.process, Endpoint(core, Token.RESOLVER_METRICS),
                None), 10.0))

        def run(coro, max_time):
            return loop.run_future(loop.spawn(coro), max_time=max_time)

        m0 = run(resolver_metrics(), 30.0)
        check(m0.get("Backend") == PLATFORM == m0.get("Platform"),
              f"the core serves with {m0.get('Backend')!r} on "
              f"{m0.get('Platform')!r}, not the {PLATFORM}")
        emit("boot", boot_seconds=round(boot_seconds, 1),
             boot_deadline_seconds=BOOT_DEADLINE_SECONDS, knobs=SERVED_KNOBS,
             **{k: m0[k] for k in BOOT_EVIDENCE})

        # ---- load: PAIRS pairs, LOAD_SETS_PER_TXN sets per transaction
        keys = sorted(model)
        txn_keys = [keys[i:i + LOAD_SETS_PER_TXN]
                    for i in range(0, PAIRS, LOAD_SETS_PER_TXN)]
        next_txn = [0]

        async def loader():
            while next_txn[0] < len(txn_keys):
                mine = txn_keys[next_txn[0]]
                next_txn[0] += 1

                async def body(tr, mine=mine):
                    for k in mine:
                        tr.set(k, model[k])
                await db.transact(body)

        async def load():
            for t in [loop.spawn(loader(), name=f"load{i}")
                      for i in range(LOADERS)]:
                await t

        t0 = time.monotonic()
        run(load(), 600.0)
        emit("load", pairs=PAIRS, transactions=len(txn_keys),
             sets_per_transaction=LOAD_SETS_PER_TXN, loaders=LOADERS,
             seconds=round(time.monotonic() - t0, 1))

        # ---- judged: read-modify-write from ACTORS concurrent actors, plus
        # pairs built to conflict (same key, same read version) and pairs
        # built not to (same read version, different keys)
        stats = {"rmw_commits": 0, "rmw_conflict_retries": 0,
                 "conflicting_pairs": 0, "disjoint_pairs": 0}
        # built pairs own their keys, so nothing else decides their fate
        built = rng.sample(range(PAIRS),
                           ACTORS * BUILT_PAIRS_PER_ACTOR * 3)
        built_keys = {key_of(i) for i in built}
        hot = [k for k in rng.sample(keys, 64) if k not in built_keys]

        async def rmw_pair(ka: bytes, kb: bytes) -> list[bool]:
            """Two transactions at ONE read version; each reads its key and
            writes it back bumped. Returns which of them committed."""
            ta, tb = db.create_transaction(), db.create_transaction()
            tb.set_read_version(await ta.get_read_version())
            for tr, k in ((ta, ka), (tb, kb)):
                tr.set(k, bump(await tr.get(k)))

            async def commit(tr) -> bool:
                try:
                    await tr.commit()
                    return True
                except FDBError as e:
                    if e.name != "not_committed":
                        raise
                    return False
            fa = loop.spawn(commit(ta), name="pairA")
            fb = loop.spawn(commit(tb), name="pairB")
            return [await fa, await fb]

        async def actor(a: int):
            arng = random.Random(seed * 1000 + a)
            for _ in range(RMW_PER_ACTOR):
                k = arng.choice(hot)
                attempts = [0]

                async def body(tr, k=k):
                    attempts[0] += 1
                    tr.set(k, bump(await tr.get(k)))
                await db.transact(body)
                model[k] = bump(model[k])
                stats["rmw_commits"] += 1
                stats["rmw_conflict_retries"] += attempts[0] - 1
            mine = built[a * BUILT_PAIRS_PER_ACTOR * 3:
                         (a + 1) * BUILT_PAIRS_PER_ACTOR * 3]
            for j in range(BUILT_PAIRS_PER_ACTOR):
                k = key_of(mine[3 * j])
                won = await rmw_pair(k, k)
                check(sorted(won) == [False, True],
                      f"conflicting pair on {k!r}: commits={won}, "
                      f"exactly one must commit")
                model[k] = bump(model[k])
                stats["conflicting_pairs"] += 1
                ka, kb = key_of(mine[3 * j + 1]), key_of(mine[3 * j + 2])
                won = await rmw_pair(ka, kb)
                check(won == [True, True],
                      f"disjoint pair on {ka!r}/{kb!r}: commits={won}, "
                      f"both must commit")
                model[ka], model[kb] = bump(model[ka]), bump(model[kb])
                stats["disjoint_pairs"] += 1

        async def judged():
            for t in [loop.spawn(actor(a), name=f"actor{a}")
                      for a in range(ACTORS)]:
                await t

        t0 = time.monotonic()
        run(judged(), 600.0)
        commits = (stats["rmw_commits"] + stats["conflicting_pairs"]
                   + 2 * stats["disjoint_pairs"])
        conflicts = (stats["rmw_conflict_retries"]
                     + stats["conflicting_pairs"])
        emit("judged", actors=ACTORS, commits=commits, conflicts=conflicts,
             **stats, seconds=round(time.monotonic() - t0, 1))

        # ---- read back every acknowledged write from storage
        async def read_back() -> int:
            compared = 0
            for lo in range(0, PAIRS, 1000):
                async def body(tr, lo=lo):
                    return await tr.get_range(key_of(lo), key_of(lo + 1000))
                rows = await db.transact(body)
                want = [(k, model[k]) for k in keys[lo:lo + 1000]]
                if rows != want:
                    bad = next((g, w) for g, w in zip(rows, want) if g != w) \
                        if len(rows) == len(want) else (len(rows), len(want))
                    fail(f"read-back of keys [{lo}, {lo + 1000}) differs "
                         f"from the model: {bad!r}")
                compared += len(rows)
            return compared

        t0 = time.monotonic()
        compared = run(read_back(), 600.0)
        check(compared == PAIRS, f"read back {compared} of {PAIRS} pairs")
        emit("readback", compared=compared, equal_to_model=True,
             seconds=round(time.monotonic() - t0, 1))

        # ---- evidence that the chip decided every batch
        m = run(resolver_metrics(), 30.0)
        check(m["Backend"] == PLATFORM, f"Backend is {m['Backend']!r}")
        check(not m["Poisoned"], "the resolver is poisoned (state overflow)")
        check(m["KernelDispatches"] >= m["BatchesIn"] > 0,
              f"KernelDispatches={m['KernelDispatches']} < commit batches "
              f"BatchesIn={m['BatchesIn']}: some batch was not decided by "
              f"the kernel")
        emit("resolver", **{k: m[k] for k in SERVED_EVIDENCE})
        client.close()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=60)
            except Exception:  # noqa: BLE001 — still reap it, then re-raise
                p.kill()
                p.wait()
                raise
        trace.g_trace_batch.dump()
        trace.set_sink(None)
        client_trace.close()
        assert_off_jax()
    return device_line(m)


# ------------------------------------------------------------ phase: kernel

def _conflict_stream(seed: int, n_batches: int, max_txns: int,
                     max_ranges: int, key_fn, n_keys: int):
    """Seeded (txns, commit_version) batches: point and short range reads
    and writes over a small keyspace (so they collide), snapshots lagging
    the commit version by up to a few batches, a few beyond the MVCC
    window (TOO_OLD)."""
    from foundationdb_tpu.ops.batch import TxnConflictInfo
    rng = random.Random(seed)
    version = 20_000_000
    for _ in range(n_batches):
        txns = []
        for _ in range(rng.randint(1, max_txns)):
            def ranges():
                out = []
                for _ in range(rng.randint(0, max_ranges)):
                    i = rng.randrange(n_keys)
                    j = i + (rng.randint(1, 8) if rng.random() < 0.2 else 0)
                    out.append((key_fn(i), key_fn(j) + b"\x00"))
                return out
            lag = (rng.randint(6_000_000, 9_000_000)
                   if rng.random() < 0.02 else rng.randint(0, 30_000))
            txns.append(TxnConflictInfo(read_snapshot=version - lag,
                                        read_ranges=ranges(),
                                        write_ranges=ranges()))
        yield txns, version
        version += rng.randint(1, 10_000)


def _window_stream(seed: int, n_batches: int, n_keys: int, step: int,
                   txns: int, sets: int):
    """Seeded (txns, commit_version) batches, `step` versions apart, of
    blind point writes uniform over `n_keys` keys, meant to be far more than
    a window's writes: every batch inserts boundaries and, once the window
    is full, the window drops about as many. One transaction in four also
    reads a key at a snapshot up to two windows old."""
    from foundationdb_tpu.ops.batch import TxnConflictInfo
    from foundationdb_tpu.utils.knobs import KNOBS
    rng = random.Random(seed)
    window = KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS

    def point():
        k = key_of(rng.randrange(n_keys))
        return (k, k + b"\x00")
    version = 20_000_000
    for _ in range(n_batches):
        version += step
        yield [TxnConflictInfo(
            read_snapshot=version - rng.randrange(2 * window),
            read_ranges=[point()] if rng.random() < 0.25 else [],
            write_ranges=[point() for _ in range(sets)])
            for _ in range(txns)], version


def _oracle_fill_step(oracle, txns, version: int, held: list[bytes]):
    """One oracle batch by the engine's own account: (verdicts, the
    boundaries held after it, rows dropped), where `held` are the boundaries
    before it and dropped = held before + new ones - held after, all over
    the collected step function (OracleConflictSet.live_boundaries)."""
    from foundationdb_tpu.ops.batch import COMMITTED
    verdicts = oracle.detect(txns, version)
    new = {k for t, s in zip(txns, verdicts) if s == COMMITTED
           for b, e in t.write_ranges if b < e for k in (b, e)}
    new.difference_update(held)
    after = oracle.live_boundaries()
    return verdicts, after, len(held) + len(new) - len(after)


def _status_counts(statuses: list[int]) -> dict:
    from foundationdb_tpu.ops.batch import COMMITTED, CONFLICT, TOO_OLD
    return {"committed": statuses.count(COMMITTED),
            "conflict": statuses.count(CONFLICT),
            "too_old": statuses.count(TOO_OLD)}


def _attach(want_count: int) -> dict:
    """Attach the accelerator in this process (every child is gone)."""
    from foundationdb_tpu.utils import jaxenv
    jaxenv.serving_platform()  # raises when JAX finds no accelerator
    device = device_line(jaxenv.device_identity())
    check(device["platform"] == PLATFORM and device["count"] == want_count,
          f"expected {want_count} {PLATFORM} device(s), JAX reports {device}")
    return device


def phase_kernel(seed: int, served_by: dict) -> None:
    """The same seeded stream through DeviceConflictSet and the independent
    oracle, at the very shapes and buckets the core served with — so every
    program comes from the compile cache the core filled."""
    from foundationdb_tpu.ops import conflict
    from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
    from foundationdb_tpu.utils.knobs import KNOBS
    device = _attach(1)
    check(device == served_by,
          f"this process sees {device}, the core served on {served_by}")
    for k, v in SERVED_KNOBS.items():
        KNOBS.set(k, v)
    t0 = time.monotonic()
    dev = conflict.DeviceConflictSet()
    dev.warmup()
    warm_seconds = time.monotonic() - t0
    oracle = OracleConflictSet()
    got_all: list[int] = []
    n_batches = 0
    t0 = time.monotonic()
    for txns, version in _conflict_stream(
            seed, n_batches=48, max_txns=600,
            max_ranges=SERVED_KNOBS["CONFLICT_BATCH_READS_PER_TXN"],
            key_fn=key_of,
            n_keys=SERVED_KNOBS["CONFLICT_STATE_CAPACITY"] // 8):
        got, want = dev.detect(txns, version), oracle.detect(txns, version)
        if got != want:
            t = next(i for i, (g, w) in enumerate(zip(got, want)) if g != w)
            fail(f"kernel vs oracle differ at batch {n_batches} txn {t}: "
                 f"device={got[t]} oracle={want[t]}")
        got_all += got
        n_batches += 1
    counts = _status_counts(got_all)
    check(all(counts.values()),
          f"the stream did not exercise every status: {counts}")
    km = conflict.kernel_metrics.as_dict()
    emit("kernel", batches=n_batches, transactions=len(got_all), **counts,
         identical_to_oracle=True, shapes=SERVED_KNOBS,
         warmup_seconds=round(warm_seconds, 1),
         detect_seconds=round(time.monotonic() - t0, 1),
         **{k: km[k] for k in KERNEL_EVIDENCE})

    # ---- the served keys, versions advancing past the window: a fill set
    # by the rate (a third of the capacity in keys a window) with an insert
    # and an eviction every step, and the step's own account of both
    capacity = SERVED_KNOBS["CONFLICT_STATE_CAPACITY"]
    txns_a_batch = SERVED_KNOBS["CONFLICT_BATCH_TXNS"]
    a_window = max(4, capacity // 3 // (txns_a_batch * OPS_PER_TXN))
    dev, oracle = conflict.DeviceConflictSet(), OracleConflictSet()
    held = oracle.live_boundaries()
    fills, dropped, got_all = [], [], []
    t0 = time.monotonic()
    for n, (txns, version) in enumerate(_window_stream(
            seed, 2 * a_window + a_window // 2, PAIRS,
            KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS // a_window,
            txns_a_batch, OPS_PER_TXN)):
        handle = dev.detect_async(txns, version)
        got = handle.result()
        want, held, evicted = _oracle_fill_step(oracle, txns, version, held)
        check(got == want, f"kernel vs oracle differ at window batch {n}")
        check(handle.steps == [(len(held), evicted)],
              f"window batch {n}: the step says (boundaries, evicted) = "
              f"{handle.steps}, the oracle ({len(held)}, {evicted})")
        fills.append(len(held))
        dropped.append(evicted)
        got_all += got
    counts = _status_counts(got_all)
    check(all(counts.values()) and all(dropped[a_window + 1:]),
          f"the window stream did not exercise every status, and the "
          f"eviction in every batch past the first window: {counts}, "
          f"rows dropped a batch {dropped}")
    check(max(fills) < capacity * 7 // 8,
          f"the window stream filled the state to {max(fills)} of {capacity}")
    emit("kernel_window", batches=len(fills), batches_a_window=a_window,
         keys=PAIRS, transactions=len(got_all), **counts,
         identical_to_oracle=True, fill_equal_to_oracle=True,
         capacity=capacity, fill_last=fills[-1], fill_peak=max(fills),
         rows_evicted=sum(dropped),
         detect_seconds=round(time.monotonic() - t0, 1))


# ----------------------------------------------------------- phase: sharded

def _clipped(txns, lo: bytes, hi: bytes | None):
    from foundationdb_tpu.ops.batch import TxnConflictInfo

    def clip(ranges):
        out = []
        for b, e in ranges:
            b2, e2 = max(b, lo), (e if hi is None else min(e, hi))
            if b2 < e2:
                out.append((b2, e2))
        return out
    return [TxnConflictInfo(read_snapshot=t.read_snapshot,
                            read_ranges=clip(t.read_ranges),
                            write_ranges=clip(t.write_ranges)) for t in txns]


def _move_oracles(oracles, old_cuts, new_cuts, at_version: int) -> None:
    """A move of the cuts as `rebalance_cuts` documents it, on the per-shard
    oracles: what a shard keeps stays exact, what it acquires is filled at
    the move's version."""
    top = b"\xff" * 32
    for o, old_lo, old_hi, lo, hi in zip(
            oracles, old_cuts, old_cuts[1:] + [top], new_cuts,
            new_cuts[1:] + [top]):
        a, b = max(lo, old_lo), min(hi, old_hi)
        if a < b:
            o.add_range(lo, a, at_version)
            o.add_range(b, hi, at_version)
        else:
            o.add_range(lo, hi, at_version)


def phase_sharded(seed: int) -> dict:
    """ShardedDeviceConflictSet over the four real devices against the
    reference for its documented semantics: one OracleConflictSet per shard
    fed the shard-clipped ranges, verdicts combined with min (the proxy's
    rule over resolvers). The keys are the benchmark's (`b"%016d"`, which
    all share their first eleven bytes), at the served shape, from a cold
    start on the default cuts with every key on shard 0; twice in
    mid-stream the cuts are moved to whole keys, and the oracles with them.
    The engine's own balance is held off so that the only moves are these,
    each at a version between two batches, which the oracles can follow."""
    import jax
    import numpy as np

    from foundationdb_tpu.ops import conflict
    from foundationdb_tpu.ops.batch import TOO_OLD, TxnConflictInfo
    from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
    from foundationdb_tpu.parallel.sharded_conflict import (
        ShardedDeviceConflictSet, make_resolver_mesh)
    from foundationdb_tpu.utils.knobs import KNOBS
    device = _attach(4)
    mesh = make_resolver_mesh(4)
    KNOBS.set("RESOLUTION_BALANCE_CHECK_BATCHES", 1 << 30)
    t0 = time.monotonic()
    cs = ShardedDeviceConflictSet(mesh=mesh, **SHARDED_SHAPE)
    cs.warmup()
    compile_seconds = time.monotonic() - t0
    cuts = list(cs.cut_bytes)

    leaves = {}
    for name, leaf in cs._state.items():
        devs = sorted(s.device.id for s in leaf.addressable_shards)
        leaves[name] = {
            "shape": list(leaf.shape), "sharding": str(leaf.sharding),
            "shard_shape": list(leaf.addressable_shards[0].data.shape),
            "devices": devs}
        check(len(set(devs)) == 4,
              f"state leaf {name!r} lives on devices {devs}, not on four")
    emit("sharded_state", mesh=str(mesh), cuts=[c.hex() for c in cuts],
         leaves=leaves)

    n_keys = SHARDED_SHAPE["capacity"] // 8
    # whole keys at the quartiles; then the eleven zeros every key shares
    # (before all of them), a key's successor, and thirteen bytes that sort
    # between two keys
    moves = {16: [b"", key_of(n_keys // 4), key_of(n_keys // 2),
                  key_of(3 * n_keys // 4)],
             32: [b"", b"0" * 11, key_of(3000) + b"\x00", b"0" * 12 + b"5"]}
    oracles = [OracleConflictSet() for _ in cuts]
    got_all: list[int] = []
    straddling: dict[str, int] = {}
    n_batches = 0
    t0 = time.monotonic()
    for txns, version in _conflict_stream(
            seed, n_batches=48, max_txns=600,
            max_ranges=SHARDED_SHAPE["reads_per_txn"], key_fn=key_of,
            n_keys=n_keys):
        for t in txns:
            for b, e in t.read_ranges + t.write_ranges:
                for c in cuts[1:]:
                    straddling[c.hex()] = straddling.get(c.hex(), 0) + (
                        b < c < e)
        got = cs.detect(txns, version)
        # the engine takes the too-old decision once, before the shards: a
        # txn with reads below the MVCC floor leaves no writes on any shard
        floor = oracles[0].oldest_version
        too_old = [bool(t.read_ranges) and t.read_snapshot < floor
                   for t in txns]
        live = [TxnConflictInfo(t.read_snapshot) if old else t
                for t, old in zip(txns, too_old)]
        per_shard = [
            o.detect(_clipped(live, lo, hi), version)
            for o, lo, hi in zip(oracles, cuts, cuts[1:] + [None])]
        want = [TOO_OLD if old else min(v)
                for old, v in zip(too_old, zip(*per_shard))]
        if got != want:
            t = next(i for i, (g, w) in enumerate(zip(got, want)) if g != w)
            fail(f"sharded vs per-shard oracles differ at batch "
                 f"{n_batches} txn {t}: device={got[t]} oracle={want[t]}")
        got_all += got
        n_batches += 1
        if n_batches in moves:
            before = np.asarray(cs._state["nb"]).tolist()
            t_move = time.monotonic()
            cs.rebalance_cuts(moves[n_batches], version)
            _move_oracles(oracles, cuts, moves[n_batches], version)
            cuts = list(cs.cut_bytes)
            emit("sharded_recut", after_batch=n_batches, version=version,
                 cuts=[c.decode("latin-1") for c in cuts],
                 boundaries_before=before,
                 boundaries_after=np.asarray(cs._state["nb"]).tolist(),
                 seconds=round(time.monotonic() - t_move, 4))
    counts = _status_counts(got_all)
    check(cs.rebalances == len(moves),
          f"{cs.rebalances} moves of the cuts, {len(moves)} were made here")
    check(all(counts.values())
          and all(any(straddling.get(c.hex()) for c in m[1:])
                  for m in moves.values()),
          f"the stream did not exercise every status and a cut of every "
          f"move: {counts}, ranges straddling each cut: {straddling}")
    km = conflict.kernel_metrics.as_dict()
    emit("sharded", batches=n_batches, transactions=len(got_all), **counts,
         ranges_straddling_each_cut=straddling, identical_to_oracle=True,
         recuts=cs.rebalances,
         boundaries=np.asarray(cs._state["nb"]).tolist(),
         ranges_offered=cs.ranges_offered, ranges_fullest=cs.ranges_fullest,
         shape=SHARDED_SHAPE, sandwich_rounds=SHARDED_SHAPE["txns"] // 2 + 1,
         compile_seconds=round(compile_seconds, 1),
         detect_seconds=round(time.monotonic() - t0, 1),
         **{k: km[k] for k in KERNEL_EVIDENCE},
         devices=[str(d) for d in jax.devices()])
    return device


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the data and every stream (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh-sharded engine vs its oracles")
    ap.add_argument("--config", help="a benchmark configuration file: its "
                    "record count, conflict knobs and loader's shape")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out"),
                    help="directory for the servers' stderr files")
    args = ap.parse_args()
    from foundationdb_tpu.utils import jaxenv
    if jaxenv.cpu_requested():
        fail("JAX_PLATFORMS=cpu: this script proves the system on the "
             "accelerator and has no CPU mode (tests/ cover the CPU)")
    if args.config:
        use_config(args.config)
    t_start = time.monotonic()
    cache_dir = jaxenv.enable_compile_cache()  # exported to every child
    os.makedirs(args.out, exist_ok=True)
    emit("start", seed=args.seed, chips=args.chips, compile_cache=cache_dir,
         cache_entries=len(os.listdir(cache_dir))
         if os.path.isdir(cache_dir) else 0)
    if args.chips == 4:
        device = phase_sharded(args.seed)
    else:
        phase_native()
        device = phase_served(args.seed, args.out)
        phase_kernel(args.seed, device)
    emit("done", wall_seconds=round(time.monotonic() - t_start, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
