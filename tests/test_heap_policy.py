"""The collector's policy of a server process (net/server_main.settle_heap)
and the one generation-2 callback that counts what full collections cost
(utils/trace.span_full_collections -> utils/stats process counters).

The policy is only ever set in a process of its own: freezing or re-tuning
the test runner's collector would change every test after this one.
"""

import gc
import json
import os
import subprocess
import sys

import pytest

from foundationdb_tpu.utils import stats
from foundationdb_tpu.utils import trace as T
from test_transport import free_port

HEAP_COUNTERS = ("FullCollections", "FullCollectionSeconds", "FrozenObjects")

# server_main.main in the main thread, as a server runs it. What the process
# looks like at the moment it says `ready` is taken from inside its own
# `print`; a helper thread then sends the SIGTERM that ends a server.
BOOT_TO_READY = """
import gc, json, os, signal, sys, threading, time
from foundationdb_tpu.net import server_main
from foundationdb_tpu.utils import stats

at_start = {"frozen": gc.get_freeze_count(), "threshold": gc.get_threshold()}
at_ready = {}
said = threading.Event()

def heard(*args, **kw):
    if str(args[0]).startswith("ready"):
        at_ready.update(frozen=gc.get_freeze_count(),
                        threshold=gc.get_threshold(),
                        watchers=len(gc.callbacks),
                        counters=stats.process_counters(),
                        garbage_left=gc.collect())
        said.set()

def stop():
    assert said.wait(60)
    while signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL, None):
        time.sleep(0.01)
    os.kill(os.getpid(), signal.SIGTERM)

server_main.print = heard
threading.Thread(target=stop, daemon=True).start()
server_main.main(sys.argv[1])
print(json.dumps({"at_start": at_start, "at_ready": at_ready,
                  "at_exit": stats.process_counters()}))
"""


def _spec(tmp_path, roles):
    return {"listen": f"127.0.0.1:{free_port()}",
            "data_dir": str(tmp_path / "data"),
            "knobs": {"CONFLICT_BACKEND": "oracle"}, "roles": roles}


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.getcwd())


# ------------------------------------------- (a) the policy, up to `ready`

@pytest.fixture(scope="module")
def booted(tmp_path_factory):
    spec = _spec(tmp_path_factory.mktemp("heap"),
                 [{"role": "master", "args": {}}])
    p = subprocess.run([sys.executable, "-c", BOOT_TO_READY, json.dumps(spec)],
                       capture_output=True, text=True, timeout=120, env=_env())
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_at_ready_what_boot_left_alive_is_frozen(booted):
    start, ready = booted["at_start"], booted["at_ready"]
    # the modules, the transport and the role: tens of thousands of objects
    assert ready["frozen"] > start["frozen"] + 10_000
    assert ready["counters"]["FrozenObjects"] == ready["frozen"]
    # boot's garbage went before the freeze: a collection straight after
    # `ready` finds next to nothing
    assert ready["garbage_left"] < 100


def test_at_ready_the_young_generation_is_the_policys(booted):
    from foundationdb_tpu.net.server_main import YOUNG_GENERATION_THRESHOLD
    start, ready = booted["at_start"], booted["at_ready"]
    assert ready["threshold"][0] == YOUNG_GENERATION_THRESHOLD
    assert ready["threshold"][0] > start["threshold"][0]
    assert ready["threshold"][1:] == start["threshold"][1:]


def test_at_ready_one_watcher_and_the_counters_start_from_nothing(booted):
    ready = booted["at_ready"]
    assert ready["watchers"] == 1  # one generation-2 callback, not two
    for name in HEAP_COUNTERS:
        assert name in ready["counters"], name
    # the boot's own collection is not counted; the one the test made at
    # `ready` is, by the time the server ends
    assert ready["counters"]["FullCollections"] == 0
    assert ready["counters"]["FullCollectionSeconds"] == 0
    assert booted["at_exit"]["FullCollections"] >= 1
    assert booted["at_exit"]["FullCollectionSeconds"] > 0


# ------------------------------------- (b) the callback, in this process

@pytest.fixture
def watcher():
    on_gc = T.span_full_collections()
    try:
        yield on_gc
    finally:
        gc.callbacks.remove(on_gc)
        T.set_sink(None)
        T.g_trace_batch._events.clear()


def test_a_full_collection_is_counted_with_its_time(watcher):
    before = stats.process_counters()
    gc.collect()
    after = stats.process_counters()
    assert after["FullCollections"] - before["FullCollections"] == 1
    took = after["FullCollectionSeconds"] - before["FullCollectionSeconds"]
    assert 0.0 <= took < 5.0
    gc.collect()
    assert (stats.process_counters()["FullCollections"]
            - before["FullCollections"]) == 2


@pytest.mark.parametrize("generation", [0, 1])
def test_a_young_collection_is_not_counted(watcher, generation):
    before = stats.process_counters()
    gc.collect(generation)
    after = stats.process_counters()
    assert after["FullCollections"] == before["FullCollections"]
    assert after["FullCollectionSeconds"] == before["FullCollectionSeconds"]


def test_no_policy_no_frozen_objects_reported():
    """The gauge says that settle_heap ran, not what the interpreter froze
    by itself: this process never set the policy."""
    assert stats.process_counters()["FrozenObjects"] == 0


def test_with_spans_on_one_pair_a_full_collection(watcher):
    got: list[dict] = []
    T.set_sink(got.append)
    before = stats.process_counters()
    gc.collect()
    gc.collect(0)
    gc.collect()
    T.g_trace_batch.dump()
    pairs = [e for e in got if e.get("Span") == "Loop.FullGC"]
    assert [e["Phase"] for e in pairs] == ["Begin", "End", "Begin", "End"]
    assert pairs[0]["ID"] == pairs[1]["ID"] != pairs[2]["ID"] == pairs[3]["ID"]
    spanned = sum(e["Time"] - b["Time"] for b, e in (pairs[:2], pairs[2:]))
    after = stats.process_counters()
    assert after["FullCollections"] - before["FullCollections"] == 2
    # the span and the counter are one measurement
    counted = after["FullCollectionSeconds"] - before["FullCollectionSeconds"]
    assert spanned == pytest.approx(counted, abs=1e-5)


def test_with_spans_off_it_counts_and_writes_nothing(watcher):
    before = stats.process_counters()["FullCollections"]
    gc.collect()
    assert T.g_trace_batch._events == []
    assert stats.process_counters()["FullCollections"] == before + 1


def test_a_counter_dump_carries_what_the_metrics_rpc_does():
    """The 5 s dumps fold the same whole-process set as a role's snapshot;
    a sim network (no transport counters) folds nothing."""
    class RealNet:
        def transport_counters(self):
            return {"FramesIn": 3}

    assert stats.whole_process_counters(object()) == {}
    extra = stats.whole_process_counters(RealNet())
    assert extra["TransportFramesIn"] == 3
    assert set(HEAP_COUNTERS) | {"LoopStalls", "ProcessCpuSeconds"} <= set(extra)
    got: list[dict] = []
    T.set_sink(got.append)
    try:
        stats.CounterCollection("Role", "addr").trace(1.0, extra=extra)
    finally:
        T.set_sink(None)
    dump, = [e for e in got if e["Type"] == "RoleMetrics"]
    for name in HEAP_COUNTERS:
        assert dump[name] == extra[name]


# ---------------------------------- (c) a spawned server's metrics RPC

def test_a_spawned_servers_metrics_rpc_returns_the_heap_counters(tmp_path):
    from foundationdb_tpu.core.sim import Endpoint
    from foundationdb_tpu.net.transport import NetTransport, RealEventLoop
    from foundationdb_tpu.server.interfaces import Token

    spec = _spec(tmp_path, [{"role": "master", "args": {}}])
    p = subprocess.Popen(
        [sys.executable, "-m", "foundationdb_tpu.net.server_main",
         json.dumps(spec)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=_env())
    client = None
    try:
        line = p.stdout.readline().decode()
        assert line.startswith("ready"), line
        loop = RealEventLoop()
        client = NetTransport(loop, f"127.0.0.1:{free_port()}")
        client.start()

        async def fetch():
            return dict(await loop.timeout(client.process.net.request(
                client.process,
                Endpoint(spec["listen"], Token.MASTER_METRICS), None), 10.0))
        snap = loop.run_future(loop.spawn(fetch()), max_time=30.0)
    finally:
        if client is not None:
            client.close()
        p.terminate()
        p.wait(timeout=10)
    for name in HEAP_COUNTERS:
        assert isinstance(snap[name], (int, float)), name
    assert snap["FrozenObjects"] > 10_000
