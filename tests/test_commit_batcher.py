"""Commit batcher behavior: what closes a batch (count / bytes / the pause
with the resolver free / a resolution's return / the cap) and the counters
that say which, the bounded multi-batch pipeline window, the empty-batch
keepalive, deterministic batch numbering under sim, and the client's AIMD
commit admission control.

Reference: MasterProxyServer.actor.cpp commitBatcher (COMMIT_TRANSACTION_
BATCH_* knobs) and GrvProxyServer's transaction budget; the pipelined
version-batch window is the reference's overlapping commitBatch actors
ordered by NotifiedVersion waits.
"""

from __future__ import annotations

import pytest

from foundationdb_tpu.core.future import Future
from foundationdb_tpu.core.sim import Endpoint
from foundationdb_tpu.server.cluster import RecoverableCluster, SimCluster
from foundationdb_tpu.server.interfaces import Token
from foundationdb_tpu.utils import trace as T
from foundationdb_tpu.utils.errors import FDBError
from foundationdb_tpu.utils.knobs import KNOBS


@pytest.fixture(autouse=True)
def _knobs():
    KNOBS.set("CONFLICT_BACKEND", "oracle")
    yield
    KNOBS.reset()


def _pump(cluster, dt: float = 0.001):
    """Run the sim loop briefly so spawned background actors start (a
    constructed-but-never-run cluster leaves them as unawaited coroutines)."""
    async def idle():
        await cluster.loop.delay(dt)
    cluster.run_all([idle()], max_time=10.0)


def _commit_n(cluster, db, n, max_time=600.0, prefix=b"cb"):
    async def one(i):
        tr = db.create_transaction()
        tr.set(b"%s%04d" % (prefix, i), b"v" * 8)
        await tr.commit()
    cluster.run_all([one(i) for i in range(n)], max_time=max_time)


# ------------------------------------------------------------ flush triggers

def test_count_trigger_flushes_before_interval():
    """COUNT_MAX reached -> the batch dispatches immediately; with the
    interval knobs set far beyond the test horizon, only the count trigger
    can explain the commits completing."""
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 4)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MIN", 30.0)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MAX", 30.0)
    c = SimCluster(seed=3, n_proxies=1)
    db = c.database()
    t0 = c.loop.now()
    _commit_n(c, db, 8, max_time=20.0)
    assert c.loop.now() - t0 < 20.0
    assert c.proxies[0]._c_batches.value >= 2


def test_bytes_trigger_flushes_before_interval():
    """BATCH_BYTES_MIN reached -> immediate dispatch, same horizon logic."""
    KNOBS.set("COMMIT_TRANSACTION_BATCH_BYTES_MIN", 64)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MIN", 30.0)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MAX", 30.0)
    c = SimCluster(seed=4, n_proxies=1)
    db = c.database()

    async def big():
        tr = db.create_transaction()
        tr.set(b"bigkey", b"x" * 200)  # alone exceeds BYTES_MIN
        await tr.commit()
    t0 = c.loop.now()
    c.run_all([big()], max_time=20.0)
    assert c.loop.now() - t0 < 20.0


def test_interval_trigger_flushes_lone_commit():
    """A single small commit (neither count nor bytes trigger) still
    dispatches after the batch interval."""
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 10_000)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_BYTES_MIN", 1 << 30)
    c = SimCluster(seed=5, n_proxies=1)
    db = c.database()
    _commit_n(c, db, 1, max_time=60.0)
    assert c.proxies[0].stats["committed"] == 1


# ------------------------------------- the pause, the return, and the cap

FLUSH_RULES = ("FlushBytes", "FlushCount", "FlushIdle", "FlushDrain",
               "FlushCap")


def _flushes(px) -> dict:
    snap = px.counters.as_dict()
    return {k: snap[k] for k in FLUSH_RULES + ("CommitBatches",)}


def _only_time_closes_a_batch(linger: float, cap: float):
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 10_000)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_BYTES_MIN", 1 << 30)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MIN", linger)
    KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MAX", cap)


def _commit_at(cluster, db, times, value=b"v" * 8, prefix=b"pa",
               max_time=600.0):
    """One commit sent at each of `times` (seconds from now); returns each
    one's latency."""
    lat = [None] * len(times)

    async def one(i, at):
        await cluster.loop.delay(at)
        t0 = cluster.loop.now()
        tr = db.create_transaction()
        tr.set(b"%s%04d" % (prefix, i), value)
        await tr.commit()
        lat[i] = cluster.loop.now() - t0
    cluster.run_all([one(i, at) for i, at in enumerate(times)],
                    max_time=max_time)
    return lat


def _clog_resolver(cluster, seconds: float):
    cluster.net.clog_pair(cluster.proxy_procs[0].address,
                          cluster.resolver_procs[0].address, seconds)


def test_lone_commit_leaves_after_the_linger_not_the_cap():
    """Resolver free, nothing else arriving: the batch leaves INTERVAL_MIN
    after its one request. With the cap at 30 s only the idle rule can
    explain a commit that completes in well under a second."""
    _only_time_closes_a_batch(linger=0.001, cap=30.0)
    c = SimCluster(seed=6, n_proxies=1)
    db = c.database()
    (lat,) = _commit_at(c, db, [0.0], max_time=20.0)
    assert lat < 0.5
    px = c.proxies[0]
    assert _flushes(px) == dict.fromkeys(FLUSH_RULES, 0) | {
        "FlushIdle": 1, "CommitBatches": 1}
    assert px._resolving == 0


def test_commits_during_a_resolution_ride_one_batch_on_its_return():
    """While a batch of the proxy's is at the resolver the next one keeps
    filling, however long the pauses between its arrivals, and leaves as
    ONE batch the moment the verdicts are back."""
    _only_time_closes_a_batch(linger=0.001, cap=30.0)
    c = SimCluster(seed=7, n_proxies=1)
    db = c.database()
    _pump(c)
    _clog_resolver(c, 0.5)  # the first batch's resolution takes 0.5 s
    lat = _commit_at(c, db, [0.0, 0.1, 0.15, 0.2, 0.3, 0.4], max_time=20.0)
    px = c.proxies[0]
    assert _flushes(px) == dict.fromkeys(FLUSH_RULES, 0) | {
        "FlushIdle": 1, "FlushDrain": 1, "CommitBatches": 2}
    # the riders waited for the first batch's return, not for any timer:
    # each is done soon after the clog lifts at 0.5 s
    assert all(l < 0.5 - at + 0.1 for l, at in
               zip(lat[1:], [0.1, 0.15, 0.2, 0.3, 0.4]))
    assert px._resolving == 0


@pytest.mark.parametrize("launch_s", [0.0, 0.1])
def test_a_batch_leaves_a_launch_time_before_the_verdicts_are_due(launch_s):
    """With one batch at the resolver, the next is held until that one's
    verdicts are due less the time a flushed batch needs to reach its step
    (both as the last batch read them), and leaves then, so that its
    version fetch and dispatch are hidden by the step before; with two
    there the third keeps filling until a return."""
    _only_time_closes_a_batch(linger=0.001, cap=30.0)
    c = SimCluster(seed=19, n_proxies=1)
    db = c.database()
    _pump(c)
    px = c.proxies[0]
    px._resolve_s, px._launch_s = 0.3, launch_s  # as if read off a batch
    _clog_resolver(c, 0.5)  # this one takes longer than that
    seen = {}

    async def look():
        await c.loop.delay(0.35)
        seen.update(flushed=px._last_flush, resolving=px._resolving,
                    pending=len(px._pending), **_flushes(px))
    t0 = c.loop.now()
    c.loop.spawn(look())
    _commit_at(c, db, [0.0, 0.1, 0.32], max_time=20.0)
    # first flush at the linger; the second 0.3 - launch_s after it
    assert abs(seen["flushed"] - (t0 + 0.001 + 0.3 - launch_s)) < 0.005
    assert (seen["resolving"], seen["pending"]) == (2, 1)
    assert (seen["FlushIdle"], seen["FlushDrain"]) == (1, 1)
    assert _flushes(px) == dict.fromkeys(FLUSH_RULES, 0) | {
        "FlushIdle": 1, "FlushDrain": 2, "CommitBatches": 3}
    assert px._resolving == 0
    # and the estimates are now this run's own readings
    assert px._resolve_s != 0.3 and px._launch_s != launch_s


@pytest.mark.parametrize("rule", ["idle", "drain"])
def test_a_pause_is_no_pause_while_the_transport_holds_unseen_input(rule):
    """On the real loop a long callback leaves requests in the process that
    _on_commit has not handled; the pause among the handled ones then
    closes nothing, neither from the timer nor at a resolution's return,
    and the batch is looked at again a linger later."""
    _only_time_closes_a_batch(linger=0.001, cap=30.0)
    c = SimCluster(seed=18, n_proxies=1)
    db = c.database()
    _pump(c)
    t0 = c.loop.now()
    c.net.input_waiting = lambda: 0.1 <= c.loop.now() - t0 < 0.5
    if rule == "idle":
        (lat,) = _commit_at(c, db, [0.2], max_time=20.0)
        assert 0.3 - 0.01 < lat < 0.3 + 0.05
        want = {"FlushIdle": 1, "CommitBatches": 1}
    else:
        _clog_resolver(c, 0.2)  # the first batch returns inside the window
        lat = _commit_at(c, db, [0.0, 0.05], max_time=20.0)
        assert 0.45 - 0.01 < lat[1] < 0.45 + 0.05
        want = {"FlushIdle": 2, "CommitBatches": 2}
    assert _flushes(c.proxies[0]) == dict.fromkeys(FLUSH_RULES, 0) | want


@pytest.mark.parametrize("closer", ["count", "bytes", "cap"])
def test_arrivals_closer_than_the_linger_keep_the_batch_open(closer):
    """Commits 5 ms apart under a 50 ms linger never show the batcher a
    pause: every batch but the last is closed by count, by bytes or by the
    cap, whichever this case leaves reachable."""
    _only_time_closes_a_batch(linger=0.05, cap=30.0)
    if closer == "count":
        KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 8)
    elif closer == "bytes":
        KNOBS.set("COMMIT_TRANSACTION_BATCH_BYTES_MIN", 8 * (6 + 50))
    else:
        KNOBS.set("COMMIT_TRANSACTION_BATCH_INTERVAL_MAX", 0.1)
    c = SimCluster(seed=8, n_proxies=1)
    db = c.database()
    lat = _commit_at(c, db, [0.005 * i for i in range(60)], value=b"x" * 50,
                     max_time=20.0)
    f = _flushes(c.proxies[0])
    rule = {"count": "FlushCount", "bytes": "FlushBytes",
            "cap": "FlushCap"}[closer]
    assert f[rule] >= 2
    assert f["FlushIdle"] + f["FlushDrain"] <= 1  # after the last arrival
    assert f[rule] + f["FlushIdle"] + f["FlushDrain"] == f["CommitBatches"]
    if closer == "cap":
        # the cap is the bound on what the batcher adds to a commit
        assert max(lat) < 0.1 + 0.05


def test_cap_closes_a_batch_whose_predecessor_is_still_resolving():
    """A resolver that does not answer must not hold the next batch for
    ever: INTERVAL_MAX after its first arrival it is flushed behind the
    one that is stuck."""
    _only_time_closes_a_batch(linger=0.001, cap=0.1)
    c = SimCluster(seed=9, n_proxies=1)
    db = c.database()
    _pump(c)
    _clog_resolver(c, 1.0)
    _commit_at(c, db, [0.0, 0.05, 0.08], max_time=20.0)
    assert _flushes(c.proxies[0]) == dict.fromkeys(FLUSH_RULES, 0) | {
        "FlushIdle": 1, "FlushCap": 1, "CommitBatches": 2}


@pytest.mark.parametrize("n_proxies,count_max,spacing", [
    (1, 10_000, 0.0), (1, 3, 0.0004), (2, 10_000, 0.003), (2, 4, 0.0)])
def test_flush_counters_sum_to_commit_batches(n_proxies, count_max, spacing):
    """Every batch that carried requests was closed by exactly one rule, on
    every proxy of the pool, and PROXY_METRICS shows the counts."""
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", count_max)
    c = SimCluster(seed=15, n_proxies=n_proxies)
    db = c.database()
    _commit_at(c, db, [spacing * i for i in range(48)])
    total = 0
    for px in c.proxies:
        f = _flushes(px)
        assert sum(f[k] for k in FLUSH_RULES) == f["CommitBatches"]
        assert px._resolving == 0 and px._inflight_batches == 0
        total += f["CommitBatches"]
    assert total >= 1
    snap = c.run(c.net.request(
        db.process, Endpoint(c.proxy_addrs[0], Token.PROXY_METRICS), None))
    assert {k: snap[k] for k in FLUSH_RULES} == {
        k: v for k, v in _flushes(c.proxies[0]).items() if k in FLUSH_RULES}


@pytest.mark.parametrize("kill_at", ["before_flush", "while_resolving"])
def test_resolving_returns_to_zero_after_a_failed_batch(kill_at):
    """A batch that fails between flush and resolution gives its place at
    the resolver back; a count left behind would hold every later batch
    until the cap."""
    _only_time_closes_a_batch(linger=0.001, cap=30.0)
    c = SimCluster(seed=16, n_proxies=1)
    db = c.database()
    _pump(c)
    resolver = c.resolver_procs[0].address
    px = c.proxies[0]
    seen = []

    async def victim():
        tr = db.create_transaction()
        tr.set(b"lost", b"v")
        with pytest.raises(FDBError) as e:
            await tr.commit()
        seen.append(e.value.name)

    async def killer():
        if kill_at == "while_resolving":
            _clog_resolver(c, 0.3)
            await c.loop.delay(0.1)
            assert px._resolving == 1
        c.net.kill(resolver)
    c.run_all([victim(), killer()], max_time=60.0)
    assert seen == ["commit_unknown_result"]
    assert px._resolving == 0 and px._inflight_batches == 0


def test_resolving_is_zero_on_every_proxy_after_a_recovery():
    """Kill the resolver's worker under load: the old generation's proxies
    fail or abandon their batches, a new generation commits, and no proxy
    object, old or new, is left counting a batch at a resolver."""
    c = RecoverableCluster(seed=17)
    db = c.database()

    def proxies():
        return [r for p in c.worker_procs if p.alive
                for k, r in p.worker.roles.items()
                if k.startswith("proxy:") and not r.grv_only]

    async def work():
        await db.refresh(max_wait=300.0)

        async def put(i):
            async def fn(tr):
                tr.set(b"rc%04d" % i, b"v")
            await db.transact(fn, max_retries=200)
        old = proxies()
        assert old
        writers = [c.loop.spawn(put(i), f"put{i}") for i in range(40)]
        await c.loop.delay(0.002)
        c.net.kill(c.current_cc().dbinfo.resolvers[0])
        for w in writers:
            await w
        await put(99)  # the new generation serves
        await c.loop.delay(1.0)
        new = [px for px in proxies() if px not in old]
        assert new, "no recovery happened"
        for px in old + new:
            assert px._resolving == 0, (px.proxy_id, px.epoch, px.dead)
        assert sum(px.counters.as_dict()["CommitBatches"] for px in new) >= 1
    c.run(c.loop.spawn(work(), "work"), max_time=60_000.0)


# ------------------------------------------------------------ pipeline window

def test_inflight_batches_bounded_by_pipeline_depth():
    """With many batches forced (COUNT_MAX=1) the number of concurrently
    in-flight version batches never exceeds COMMIT_PIPELINE_DEPTH, and the
    pipeline actually overlaps batches (depth observed > 1)."""
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 1)
    KNOBS.set("COMMIT_PIPELINE_DEPTH", 2)
    c = SimCluster(seed=8, n_proxies=1)
    px = c.proxies[0]
    seen: list[int] = []
    orig = px._flush

    def spy():
        orig()
        seen.append(px._inflight_batches)
    px._flush = spy
    db = c.database()
    _commit_n(c, db, 30)
    assert seen and max(seen) <= 2
    assert max(seen) > 1, "pipeline never overlapped two batches"
    assert px._inflight_batches == 0  # every batch released its slot


def test_depth_one_serializes_batches():
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", 1)
    KNOBS.set("COMMIT_PIPELINE_DEPTH", 1)
    c = SimCluster(seed=9, n_proxies=1)
    px = c.proxies[0]
    seen: list[int] = []
    orig = px._flush

    def spy():
        orig()
        seen.append(px._inflight_batches)
    px._flush = spy
    db = c.database()
    _commit_n(c, db, 12)
    assert seen and max(seen) == 1
    assert px._inflight_batches == 0


# -------------------------------------------------------- empty-batch keepalive

def test_empty_batch_keepalive_advances_committed_version():
    """An idle proxy still pushes empty batches every IDLE_INTERVAL so
    storage servers' version horizon (and GRV recency) keeps moving."""
    c = SimCluster(seed=10, n_proxies=1)
    px = c.proxies[0]
    # statically-built sim proxies don't start the keepalive (it exists for
    # recruited clusters whose storage horizon must keep moving); start it
    # here to test the loop itself
    px._empty_task = px.process.spawn(px._empty_batch_loop(), "emptyBatch")

    async def idle():
        await c.loop.delay(5 * KNOBS.COMMIT_BATCH_IDLE_INTERVAL)
    c.run_all([idle()], max_time=60.0)
    assert px.committed_version.get() > 0
    assert px.stats["commits_in"] == 0


# ------------------------------------------------- deterministic numbering

def _batch_ids(seed: int, count_max: int = 2) -> list[str]:
    got: list[dict] = []
    KNOBS.set("COMMIT_TRANSACTION_BATCH_COUNT_MAX", count_max)
    KNOBS.set("COMMIT_PIPELINE_DEPTH", 4)
    T.g_trace_batch._events.clear()  # drop other tests' buffered records
    try:
        T.set_sink(got.append)
        c = SimCluster(seed=seed, n_proxies=2)
        db = c.database()
        _commit_n(c, db, 24)
        T.g_trace_batch.dump()
    finally:
        T.set_sink(None)
        T.g_trace_batch._events.clear()
    return [e["ID"] for e in got
            if e.get("Span") == "Proxy.BatchAssembly"
            and e.get("Phase") == "Begin"]


def test_batch_numbering_deterministic_with_pipelining():
    """Same seed => identical batch-id sequence even with a >1 pipeline
    window (batch numbers are assigned at flush, not at completion)."""
    a = _batch_ids(seed=21)
    b = _batch_ids(seed=21)
    assert a and a == b
    # distinct per-proxy monotonic numbering, no reuse
    assert len(a) == len(set(a))


def test_batch_numbering_deterministic_under_the_pause_rule():
    """The same with two proxies and no count trigger in reach: the batches
    are cut by the pause and the return rules, which read only the virtual
    clock and the proxy's own counts."""
    a = _batch_ids(seed=22, count_max=10_000)
    b = _batch_ids(seed=22, count_max=10_000)
    assert a and a == b
    assert len(a) == len(set(a))
    assert {i.split(".")[0] for i in a} == {"b0", "b1"}


# ------------------------------------------------------ client admission

def test_admission_bounds_in_flight_commits():
    KNOBS.set("CLIENT_COMMIT_INITIAL_IN_FLIGHT", 3)
    KNOBS.set("CLIENT_COMMIT_MAX_IN_FLIGHT", 3)
    c = SimCluster(seed=12, n_proxies=1)
    db = c.database()
    peak = [0]
    done = [0]

    async def monitor():
        while done[0] < 20:
            peak[0] = max(peak[0], db._commits_in_flight)
            await c.loop.delay(0.0002)

    async def one(i):
        tr = db.create_transaction()
        tr.set(b"adm%04d" % i, b"v")
        await tr.commit()
        done[0] += 1
    c.run_all([monitor()] + [one(i) for i in range(20)], max_time=600.0)
    assert peak[0] <= 3
    assert db._commits_in_flight == 0 and not db._commit_queue


def test_admission_feedback_aimd():
    c = SimCluster(seed=13, n_proxies=1)
    _pump(c)
    db = c.database()
    db._commit_budget = 8.0

    ok = Future()
    ok._set(object())
    # healthy acks: additive increase, bounded by MAX
    db._admission_feedback(ok, 0.010)
    assert db._commit_budget > 8.0
    db._commit_budget = float(KNOBS.CLIENT_COMMIT_MAX_IN_FLIGHT)
    db._admission_feedback(ok, 0.010)
    assert db._commit_budget == float(KNOBS.CLIENT_COMMIT_MAX_IN_FLIGHT)

    # throttle signal: multiplicative cut, floored at 1
    db._commit_budget = 10.0
    throttled = Future()
    throttled._set_error(FDBError("transaction_throttled", "0.1 00 ff"))
    db._admission_feedback(throttled, 0.001)
    assert db._commit_budget == pytest.approx(
        10.0 * KNOBS.CLIENT_ADMISSION_DECREASE)
    # a second cut inside the same window is suppressed (one cut per event)
    db._admission_feedback(throttled, 0.001)
    assert db._commit_budget == pytest.approx(
        10.0 * KNOBS.CLIENT_ADMISSION_DECREASE)

    # latency inflation vs the learned floor also cuts
    db2 = c.database("client:aimd2")
    db2._commit_budget = 10.0
    db2._admission_feedback(ok, 0.010)  # learn the floor
    assert db2._commit_lat_floor == pytest.approx(0.010)
    db2._admission_feedback(
        ok, 0.010 * (KNOBS.CLIENT_ADMISSION_LATENCY_RATIO + 1))
    assert db2._commit_budget < 10.0

    # conflicts say nothing about queueing: budget untouched
    db3 = c.database("client:aimd3")
    db3._commit_budget = 10.0
    conflict = Future()
    conflict._set_error(FDBError("not_committed"))
    db3._admission_feedback(conflict, 0.010)
    assert db3._commit_budget == 10.0


def test_one_fast_commit_is_not_the_admission_baseline():
    """A commit that found the resolver free skips a whole resolution and
    comes back several times faster than the rest, with no queueing
    anywhere: the budget must be judged against the level commits run at,
    not against that one, or ordinary commits after it read as inflated."""
    c = SimCluster(seed=14, n_proxies=1)
    _pump(c)
    db = c.database()
    db._commit_budget = 10.0
    ok = Future()
    ok._set(object())
    for _ in range(50):
        db._admission_feedback(ok, 0.020)
    db._admission_feedback(ok, 0.002)  # the resolver-free path
    budget = db._commit_budget
    db._admission_feedback(ok, 0.030)  # 15x the fast one, 1.5x the level
    assert db._commit_budget > budget
    # a real jump against the level still cuts
    db._admission_feedback(
        ok, 0.020 * (KNOBS.CLIENT_ADMISSION_LATENCY_RATIO + 1))
    assert db._commit_budget < budget


# ------------------------------------------------- grv/commit proxy split

def test_grv_split_recruited_and_routed():
    """The CC recruits dedicated GRV proxies on their own workers, the
    DBInfo publishes them, and a refreshed client routes read versions to
    the GRV pool while commits stay on the commit pool."""
    c = RecoverableCluster(seed=11, n_workers=5, n_proxies=2,
                           n_grv_proxies=1, n_resolvers=1, n_tlogs=2,
                           n_storage=2)
    db = c.database()

    async def work():
        await db.refresh(max_wait=300.0)
        assert db.grv_proxies, "grv pool empty after refresh"
        assert not set(db.grv_proxies) & set(db.proxies), \
            "grv proxy co-listed in the commit pool"

        async def fn(tr):
            tr.set(b"split", b"1")
        await db.transact(fn, max_retries=50)
        tr = db.create_transaction()
        assert await tr.get(b"split") == b"1"
        status = await db.get_status()
        roles = [e["role"] for e in status["cluster"]["roles"]]
        assert "grv_proxy" in roles
    c.run(c.loop.spawn(work(), "work"), max_time=60_000.0)
