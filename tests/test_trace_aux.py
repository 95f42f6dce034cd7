"""Auxiliary observability: rolling trace files, rate suppression,
g_traceBatch txn timelines, latency bands, AsyncVar/AsyncTrigger.

Reference: flow/Trace.cpp (rolling + suppression), flow/Trace.h g_traceBatch,
flow/Stats.h LatencyBands, flow/genericactors.actor.h AsyncVar/AsyncTrigger.
"""

from __future__ import annotations

import json
import os

import pytest

from foundationdb_tpu.utils import trace as T


@pytest.fixture(autouse=True)
def _clean_trace():
    yield
    T.set_sink(None)
    T.disable_suppression()


def test_rolling_trace_file(tmp_path):
    path = str(tmp_path / "trace.log")
    rt = T.RollingTraceFile(path, roll_bytes=500, keep=3)
    T.set_sink(rt.write)
    for i in range(100):
        T.TraceEvent("RollMe").detail("I", i).log()
    rt.close()
    rolls = [f for f in os.listdir(tmp_path) if f.startswith("trace.log.")]
    assert rolls, "never rolled"
    assert len(rolls) <= 3
    # every kept file parses as JSON lines
    for name in rolls + ["trace.log"]:
        for line in open(tmp_path / name):
            json.loads(line)


def test_rolling_trace_file_keep_chain(tmp_path):
    """Explicit rolls shift path.1 -> path.2 -> ... and drop past `keep`;
    the newest roll always holds the newest content."""
    path = str(tmp_path / "trace.log")
    rt = T.RollingTraceFile(path, roll_bytes=10**9, keep=2)
    T.set_sink(rt.write)
    for gen in range(4):
        T.TraceEvent("Gen").detail("N", gen).log()
        rt.roll()
    rt.close()
    names = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("trace.log."))
    assert names == ["trace.log.1", "trace.log.2"]  # 3rd+ oldest dropped
    newest = [json.loads(line) for line in open(tmp_path / "trace.log.1")]
    assert newest[-1]["N"] == 3
    older = [json.loads(line) for line in open(tmp_path / "trace.log.2")]
    assert older[-1]["N"] == 2


def test_suppression_flush_on_quiet():
    """A chatty type that goes quiet still surfaces its final window's
    Dropped count via flush_suppressed()."""
    got: list[dict] = []
    T.set_sink(got.append)
    T.enable_suppression(limit=3, interval=10_000.0)
    for _ in range(10):
        T.TraceEvent("Chatty").log()
    assert not [e for e in got if e["Type"] == "TraceEventsSuppressed"]
    T.flush_suppressed()
    sup = [e for e in got if e["Type"] == "TraceEventsSuppressed"]
    assert len(sup) == 1
    assert sup[0]["OfType"] == "Chatty" and sup[0]["Dropped"] == 7
    # flushed windows reset: a second flush reports nothing new
    T.flush_suppressed()
    assert len([e for e in got if e["Type"] == "TraceEventsSuppressed"]) == 1


def test_sampling_profiler_catches_a_hot_loop():
    import time as wall

    from foundationdb_tpu.utils.profiler import SamplingProfiler

    def hot_spin(deadline):
        x = 0
        while wall.perf_counter() < deadline:
            x += 1
        return x

    p = SamplingProfiler(interval=0.001)
    p.start()
    hot_spin(wall.perf_counter() + 0.25)
    report = p.stop()
    assert p.total_samples > 0 and report
    hottest = p.hottest_functions(top=5)
    assert any("hot_spin" in label for label, _n in hottest), hottest
    got: list[dict] = []
    T.set_sink(got.append)
    p.trace_report(who="test")
    assert any(e["Type"] == "ProfilerSample" and "hot_spin" in e["Where"]
               for e in got)


def test_latency_bands_exact_edges():
    """Band assignment at the boundaries: a sample exactly ON an upper
    bound lands in that bound's band (bisect_left semantics)."""
    lb = T.LatencyBands("Edges")
    first, last = T.LatencyBands.BANDS[0], T.LatencyBands.BANDS[-1]
    lb.add(0.0)          # below everything -> first band
    lb.add(first)        # exactly the first bound -> still le_first
    lb.add(last)         # exactly the last bound -> le_last, not gt
    lb.add(last + 1e-9)  # just past it -> overflow bucket
    got: list[dict] = []
    T.set_sink(got.append)
    lb.trace()
    ev = got[0]
    assert ev[f"le_{first}"] == 2
    assert ev[f"le_{last}"] == 1
    assert ev["gt_last"] == 1
    assert ev["Total"] == 4 and ev["Max"] == round(last + 1e-9, 6)


def test_suppression_limits_and_reports(tmp_path):
    got: list[dict] = []
    T.set_sink(got.append)
    T.enable_suppression(limit=5, interval=1000.0)
    for _ in range(50):
        T.TraceEvent("Chatty").log()
    T.TraceEvent("Rare").log()
    # errors always pass
    for _ in range(10):
        T.TraceEvent("Bad", severity=T.SevError).log()
    chatty = [e for e in got if e["Type"] == "Chatty"]
    assert len(chatty) == 5
    assert len([e for e in got if e["Type"] == "Rare"]) == 1
    assert len([e for e in got if e["Type"] == "Bad"]) == 10


def test_trace_batch_timeline():
    tb = T.TraceBatch()
    tb.add_event("CommitDebug", "txn1", "Native.commit.Before")
    tb.add_event("CommitDebug", "txn2", "Native.commit.Before")
    tb.add_event("CommitDebug", "txn1", "Proxy.commitBatch.AfterResolution")
    tl = tb.timeline("txn1")
    assert [e["Location"] for e in tl] == [
        "Native.commit.Before", "Proxy.commitBatch.AfterResolution"]
    got: list[dict] = []
    T.set_sink(got.append)
    tb.dump()
    assert len(got) == 3 and tb.timeline("txn1") == []


def test_latency_bands():
    lb = T.LatencyBands("X")
    for s in (0.0005, 0.003, 0.003, 0.2, 9.0):
        lb.add(s)
    got: list[dict] = []
    T.set_sink(got.append)
    lb.trace()
    ev = got[0]
    assert ev["Type"] == "XLatencyBands"
    assert ev["Total"] == 5
    assert ev["le_0.001"] == 1
    assert ev["le_0.005"] == 2
    assert ev["gt_last"] == 1


def test_async_var_and_trigger():
    from foundationdb_tpu.core.eventloop import EventLoop
    from foundationdb_tpu.core.notified import AsyncTrigger, AsyncVar

    loop = EventLoop()
    av = AsyncVar(1)
    trig = AsyncTrigger()
    seen = []

    async def watcher():
        seen.append(await av.on_change())
        await trig.on_trigger()
        seen.append("triggered")

    async def driver():
        av.set(1)  # no-op: equal value must not fire
        await loop.delay(0.01)
        av.set(2)
        await loop.delay(0.01)
        trig.trigger()
        await loop.delay(0.01)
        trig.trigger()  # no waiter: forgotten, not queued

    t1 = loop.spawn(watcher(), name="w")
    t2 = loop.spawn(driver(), name="d")
    loop.run_future(t2, max_time=10.0)
    assert seen == [2, "triggered"]
    assert av.get() == 2


def test_proxy_emits_bands_and_probes():
    """The live proxy records commit/GRV latency bands and CommitDebug
    timeline probes."""
    from foundationdb_tpu.server.cluster import SimCluster
    from foundationdb_tpu.utils.knobs import KNOBS

    KNOBS.set("CONFLICT_BACKEND", "oracle")
    got: list[dict] = []
    T.set_sink(got.append)  # probes are recorded only while someone listens
    try:
        c = SimCluster(seed=2, n_proxies=1, n_resolvers=1, n_tlogs=1,
                       n_storage=1)
        db = c.database()

        async def t():
            for i in range(5):
                tr = db.create_transaction()
                await tr.get(b"k%d" % i)  # forces a GRV
                tr.set(b"k%d" % i, b"v")
                await tr.commit()
        c.run(c.loop.spawn(t()), max_time=600.0)
        p = c.proxies[0]
        assert p.commit_bands.total >= 5
        assert p.grv_bands.total >= 1
        T.g_trace_batch.dump()
    finally:
        T.set_sink(None)
        KNOBS.reset()
    probes = [e for e in got if e["Type"] == "CommitDebug"]
    assert any(e["Location"] == "Proxy.commitBatch.AfterLogPush"
               for e in probes)


def test_sim_validation_oracles():
    """sim_validation (fdbrpc/sim_validation.cpp pattern): the external-
    consistency oracle observes real multi-proxy runs, and violations
    assert."""
    from foundationdb_tpu.core import sim_validation as sv
    from foundationdb_tpu.server.cluster import SimCluster
    from foundationdb_tpu.utils.knobs import KNOBS

    KNOBS.set("CONFLICT_BACKEND", "oracle")
    c = SimCluster(seed=6, n_proxies=2, n_resolvers=1, n_tlogs=1, n_storage=1)
    oracle = sv.of(c.net)
    assert oracle.enabled
    # a second simulated cluster in the same interpreter gets its OWN oracle
    # (state is per-SimNetwork, not module-global)
    c2 = SimCluster(seed=7, n_proxies=1, n_resolvers=1, n_tlogs=1, n_storage=1)
    assert sv.of(c2.net) is not oracle

    async def t():
        for i in range(10):
            tr = db.create_transaction()
            await tr.get(b"s%d" % i)
            tr.set(b"s%d" % i, b"v")
            await tr.commit()
    db = c.database()
    c.run(c.loop.spawn(t()), max_time=600.0)
    assert oracle.debug_grv_floor() > 0  # acks were observed
    assert sv.of(c2.net).debug_grv_floor() == 0  # and c2's saw none of them

    # a violating sequence asserts (the oracle has teeth)
    oracle.debug_advance_max_committed(10**15, "pA/b1")
    with pytest.raises(AssertionError):
        oracle.debug_advance_max_committed(10**15, "pB/b9")
    with pytest.raises(AssertionError):
        oracle.debug_check_read_version(1, 10**15, "pA")
    KNOBS.reset()
