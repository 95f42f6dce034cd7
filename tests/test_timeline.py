"""One timeline for the core process: the span buffer's off switch, the
section helper and its annotator hook, the slow-task watch on the real loop,
the process-wide counters in every role's snapshot, the step program's name
and scopes, and the transfer counters on the served path.

Reference: flow/Trace.h g_traceBatch, flow/Net2.actor.cpp SlowTask.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np
import pytest

from foundationdb_tpu.utils import stats
from foundationdb_tpu.utils import trace as T


@pytest.fixture(autouse=True)
def _clean_trace():
    T.g_trace_batch._events.clear()
    annotator = T._annotator
    yield
    T.set_sink(None)
    T.set_annotator(annotator)
    T.g_trace_batch._events.clear()


def _record_everything(tb: T.TraceBatch) -> None:
    tb.span_begin("CommitSpan", "v1", "Stage", at=1.0)
    tb.span_end("CommitSpan", "v1", "Stage", at=2.0)
    tb.add_event("CommitDebug", "v1", "Somewhere", at=1.5)
    tb.add_attach("CommitAttach", "b0.1", "v1", at=1.5)
    with tb.section("CommitSpan", "v1", "Section"):
        pass
    with tb.annotate("Section", "v1"):
        pass


# ------------------------------------------------------------------ the off

def test_no_sink_no_record_and_nothing_written(capsys):
    calls = []
    T.set_annotator(lambda *a: calls.append(a) or contextlib.nullcontext())
    assert T.g_trace_batch.enabled is False
    _record_everything(T.g_trace_batch)
    assert T.g_trace_batch._events == []
    assert calls == []  # off is off for the annotation too
    assert T.g_trace_batch.section("CommitSpan", "v1", "S") is T._NULL_SECTION
    T.g_trace_batch.dump()
    out = capsys.readouterr()
    assert out.err == "" and out.out == ""


def test_a_sink_turns_the_spans_on_and_its_removal_off_again():
    got: list[dict] = []
    T.set_sink(got.append)
    assert T.g_trace_batch.enabled is True
    _record_everything(T.g_trace_batch)
    T.g_trace_batch.dump()
    assert len(got) == 6
    T.set_sink(None)
    _record_everything(T.g_trace_batch)
    assert T.g_trace_batch._events == [] and len(got) == 6


def test_a_dump_without_a_sink_drops_and_prints_nothing(capsys):
    tb = T.TraceBatch()  # a batch built by hand records from the start
    tb.span_begin("CommitSpan", "x", "Stage", at=1.0)
    tb.dump()
    assert tb._events == [] and capsys.readouterr().err == ""


def test_trace_events_still_reach_stderr_without_a_sink(capsys):
    T.TraceEvent("StillHere").detail("K", 1).log()
    assert '"Type": "StillHere"' in capsys.readouterr().err


def test_the_rolling_file_buffers_span_records_and_flushes_on_an_event(tmp_path):
    path = tmp_path / "trace.jsonl"
    rt = T.RollingTraceFile(str(path))
    T.set_sink(rt.write)
    tb = T.TraceBatch()
    for i in range(50):
        tb.span_begin("CommitSpan", f"v{i}", "Stage", at=float(i))
    tb.add_attach("CommitAttach", "b0.1", "v1", at=1.0)
    tb.dump()
    assert path.read_text() == ""  # one write for the lot, later
    T.TraceEvent("Now").log()      # an event reaches the file at once
    lines = path.read_text().splitlines()
    assert len(lines) == 52 and '"Type": "Now"' in lines[-1]
    tb.span_end("CommitSpan", "v0", "Stage", at=60.0)
    tb.dump()
    rt.close()
    assert len(path.read_text().splitlines()) == 53


# -------------------------------------------------------------- the section

class _Annotation:
    def __init__(self, log, *args):
        self.log, self.args = log, args

    def __enter__(self):
        self.log.append(("enter",) + self.args)

    def __exit__(self, *exc):
        self.log.append(("exit", exc[0]))
        return False


@pytest.mark.parametrize("raises", [False, True])
def test_section_writes_a_pair_and_calls_the_annotator(raises):
    from foundationdb_tpu.tools import trace_analyze as TA
    log: list = []
    T.set_annotator(lambda *a: _Annotation(log, *a))
    tb = T.TraceBatch()
    clock = iter([10.0, 10.5])
    before = time.monotonic()
    try:
        with tb.section("CommitSpan", 7, "Resolver.Dispatch",
                        now=lambda: next(clock)):
            log.append("body")
            if raises:
                raise KeyError("boom")
    except KeyError:
        assert raises
    after = time.monotonic()
    begin, end = tb._events  # the End is written whatever the body did
    assert begin == {"Type": "CommitSpan", "Time": 10.0, "ID": "7",
                     "Span": "Resolver.Dispatch", "Phase": "Begin"}
    assert end == dict(begin, Time=10.5, Phase="End")
    assert TA.check_well_formed(tb._events) == []
    (_enter, span, ident, mono_us), body, (_exit, exc_type) = log
    assert (span, ident, body) == ("Resolver.Dispatch", "7", "body")
    assert exc_type is (KeyError if raises else None)
    assert isinstance(mono_us, int)
    assert before - 1e-3 <= mono_us / 1e6 <= after + 1e-3


def test_section_without_an_annotator_and_the_default_clock():
    T.set_annotator(None)
    tb = T.TraceBatch()
    with tb.section("CommitSpan", "v2", "Resolver.Encode"):
        pass
    begin, end = tb._events
    assert begin["Phase"] == "Begin" and end["Phase"] == "End"
    assert abs(begin["Time"] - time.monotonic()) < 1.0  # not the wall clock
    assert tb.annotate("X", "v2") is T._NULL_SECTION


def test_the_device_engine_installs_the_profilers_annotation():
    import jax

    from foundationdb_tpu.ops import conflict
    T.set_annotator(None)
    conflict.DeviceConflictSet(capacity=64, txns=4, reads_per_txn=2,
                               writes_per_txn=2)
    ann = T._annotator("Resolver.Dispatch", "v9", 123)
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    with ann:  # no profile is being taken: a no-op that must not raise
        pass


# ---------------------------------------------------- the slow-task watch

def _run(loop, seconds: float, block: float = 0.0):
    def holds_the_loop():
        time.sleep(block)

    async def main():
        await loop.delay(0.15)
        if block:
            holds_the_loop()
        await loop.delay(seconds)
    loop.run_future(loop.spawn(main()), max_time=30.0)


def test_a_held_loop_logs_one_slow_task_with_the_holders_stack():
    from foundationdb_tpu.net.transport import RealEventLoop
    got: list[dict] = []
    T.set_sink(got.append)
    before = stats.process_counters()
    loop = RealEventLoop()
    _run(loop, 0.2, block=0.4)
    T.g_trace_batch.dump()
    after = stats.process_counters()
    assert after["LoopStalls"] - before["LoopStalls"] == 1
    assert 0.3 <= after["LoopStallSeconds"] - before["LoopStallSeconds"] <= 0.6
    assert after["LoopStallMaxSeconds"] >= 0.3
    events = [e for e in got if e["Type"] == "SlowTask"]
    assert len(events) == 1
    ev = events[0]
    assert 0.3 <= ev["Duration"] <= 0.6
    assert ev["Leaf"].startswith("holds_the_loop (")
    assert len(ev["Stack"]) <= 12
    assert any(f.startswith("holds_the_loop") for f in ev["Stack"])
    # what the benchmark's sink drops: span, attach and probe records
    assert not {"Span", "To", "Location"} & set(ev)
    pair = [e for e in got if e.get("Span") == "Loop.SlowTask"]
    assert [e["Phase"] for e in pair] == ["Begin", "End"]
    assert pair[1]["Time"] - pair[0]["Time"] == pytest.approx(
        ev["Duration"], abs=1e-5)


def test_a_ticking_or_resting_loop_logs_none():
    from foundationdb_tpu.net.transport import RealEventLoop
    got: list[dict] = []
    T.set_sink(got.append)
    before = stats.process_counters()
    loop = RealEventLoop()
    _run(loop, 1.0)
    time.sleep(0.5)  # not running: at rest, not held
    _run(loop, 0.3)
    assert stats.process_counters()["LoopStalls"] == before["LoopStalls"]
    assert not [e for e in got if e["Type"] == "SlowTask"]


def test_the_watch_ends_with_its_loop():
    import gc

    from foundationdb_tpu.net.transport import RealEventLoop
    loop = RealEventLoop()
    _run(loop, 0.0)
    watch = loop._watch
    assert watch.is_alive()
    del loop
    gc.collect()
    watch.join(timeout=2.0)
    assert not watch.is_alive()


def test_a_full_collection_writes_a_span_pair_while_someone_listens():
    import gc
    on_gc = T.span_full_collections()
    try:
        gc.collect()  # no sink: no record
        assert T.g_trace_batch._events == []
        got: list[dict] = []
        T.set_sink(got.append)
        gc.collect(0)  # a young collection is not a stall
        gc.collect()
        T.g_trace_batch.dump()
    finally:
        gc.callbacks.remove(on_gc)
    begin, end = got
    assert begin["Span"] == end["Span"] == "Loop.FullGC"
    assert (begin["Phase"], end["Phase"]) == ("Begin", "End")
    assert begin["ID"] == end["ID"] and begin["Time"] <= end["Time"]
    assert abs(end["Time"] - time.monotonic()) < 1.0


# ------------------------------------- process counters in every snapshot

PROCESS_COUNTERS = ("ProcessCpuSeconds", "LoopStalls", "LoopStallSeconds",
                    "LoopStallMaxSeconds", "FullCollections",
                    "FullCollectionSeconds", "FrozenObjects")


@pytest.fixture(scope="module")
def sim_roles():
    from foundationdb_tpu.server.cluster import SimCluster
    from foundationdb_tpu.utils.knobs import KNOBS
    KNOBS.set("CONFLICT_BACKEND", "oracle")
    try:
        c = SimCluster(seed=5, n_proxies=1, n_resolvers=1, n_tlogs=1,
                       n_storage=1)
        from foundationdb_tpu.server.ratekeeper import Ratekeeper
        yield {"master": c.master, "proxy": c.proxies[0],
               "resolver": c.resolvers[0], "tlog": c.tlogs[0],
               "storage": c.storages[0],
               "ratekeeper": Ratekeeper(c.master_proc)}
    finally:
        KNOBS.reset()


@pytest.mark.parametrize("kind", ["master", "proxy", "resolver", "tlog",
                                  "storage", "ratekeeper"])
def test_every_roles_snapshot_carries_the_process_counters(sim_roles, kind):
    """On a real transport (one that has transport counters); a simulated
    process shares its interpreter with the whole cluster and reports none."""
    role = sim_roles[kind]

    class Reply:
        def send(self, snap):
            self.snap = snap

    sim_reply = Reply()
    role._on_metrics(None, sim_reply)
    assert not set(PROCESS_COUNTERS) & set(sim_reply.snap)
    net = role.process.net
    net.transport_counters = lambda: {"FramesIn": 3}
    try:
        real_reply = Reply()
        role._on_metrics(None, real_reply)
    finally:
        del net.transport_counters
    snap = real_reply.snap
    assert snap["TransportFramesIn"] == 3
    for name in PROCESS_COUNTERS:
        assert isinstance(snap[name], (int, float)), name
    assert snap["ProcessCpuSeconds"] > 0


# ------------------------------------------ the step program: name, scopes

SMALL = dict(capacity=64, txns=4, reads_per_txn=2, writes_per_txn=2)


def _lowered(jitted, *args) -> str:
    return jitted.lower(*args).as_text(debug_info=True)


@pytest.fixture(scope="module")
def step_text():
    from foundationdb_tpu.ops import conflict
    from foundationdb_tpu.utils.knobs import KNOBS
    shapes = conflict._resolve_shapes(**SMALL)
    step = conflict._compiled_step(
        shapes, KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS)
    state = conflict.init_state(shapes)
    batch = conflict.BatchEncoder(shapes).encode_batch([], 1, shapes=shapes)
    batch["advance_floor"] = np.bool_(True)
    return _lowered(step, state, batch)


def test_the_step_program_is_called_jit_conflict_step(step_text):
    assert "module @jit_conflict_step " in step_text


@pytest.mark.parametrize("scope", ["sort", "history", "intra", "merge", "gc",
                                   "table"])
def test_the_step_programs_text_holds_the_scope(step_text, scope):
    from foundationdb_tpu.ops import conflict
    assert scope in conflict.SCOPES
    assert f"jit(conflict_step)/{scope}/" in step_text


def test_the_other_programs_have_names_too():
    import jax.numpy as jnp

    from foundationdb_tpu.ops import conflict
    shapes = conflict._resolve_shapes(**SMALL)
    t = jnp.zeros(shapes.txns, jnp.int32)
    text = _lowered(conflict._combine_fn(), t, t.astype(bool), False, True,
                    np.int32(1), np.int32(0))
    assert "module @jit_combine_status " in text
    text = _lowered(conflict._compiled_rebase(), conflict.init_state(shapes),
                    np.int32(5))
    assert "module @jit_rebase_state " in text


# ----------------------------------------------------- transfer counters

def test_transfer_counters_rise_by_the_batch_and_the_status_array():
    from foundationdb_tpu.ops import conflict
    from foundationdb_tpu.ops.batch import TxnConflictInfo
    from foundationdb_tpu.utils import jaxenv
    cs = conflict.DeviceConflictSet(**SMALL)
    txns = [TxnConflictInfo(read_snapshot=5, read_ranges=[(b"a", b"b")],
                            write_ranges=[(b"a", b"b")])]
    # what crosses: the encoded batch in (with its floor flag), and the
    # combined status array [statuses | eligible | overflow | converged |
    # boundaries | evicted] out: the state's fill and churn ride the verdicts
    nr, nw = 1, 1
    shapes, _step = cs.plan_chunk(nr, nw)
    batch = conflict.BatchEncoder(shapes).encode_batch(txns, 10, shapes=shapes)
    batch["advance_floor"] = np.bool_(True)
    batch_bytes = sum(np.asarray(v).nbytes for v in batch.values())
    status_bytes = (2 * shapes.txns + 4) * 4
    before = jaxenv.transfer_metrics.as_dict()
    assert cs.detect(txns, 10) == [conflict.COMMITTED]
    after = jaxenv.transfer_metrics.as_dict()
    assert after["DevicePuts"] - before["DevicePuts"] == 1
    assert after["DevicePutBytes"] - before["DevicePutBytes"] == batch_bytes
    assert after["DeviceGets"] - before["DeviceGets"] == 1
    assert after["DeviceGetBytes"] - before["DeviceGetBytes"] == status_bytes
