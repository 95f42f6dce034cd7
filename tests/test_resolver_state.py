"""The one-chip resolver's account of its device state: fill and churn as
counters, a word before the state is full, and what happens when it is.

A Resolver role alone on a simulated process, the device engine on the CPU at
a small capacity, batches sent as a proxy sends them. What each step left in
the state comes back with its verdicts (DetectHandle.steps); the role sums it
up in `StateBoundariesSum`, `StateCapacitySum`, `StateBoundariesPeak` and
`StateEvictedSum`, says `ResolverStateNearFull` once each time a step leaves
the state above 7/8 of its capacity, and an overflow poisons it: that batch
and every later one are answered with the error.
"""

import pytest

from foundationdb_tpu.core.eventloop import EventLoop
from foundationdb_tpu.core.sim import Endpoint, SimNetwork
from foundationdb_tpu.ops.batch import COMMITTED, TxnConflictInfo
from foundationdb_tpu.server.interfaces import (
    ResolveTransactionBatchRequest, Token)
from foundationdb_tpu.server.resolver import Resolver
from foundationdb_tpu.utils import trace
from foundationdb_tpu.utils.errors import FDBError
from foundationdb_tpu.utils.knobs import KNOBS
from foundationdb_tpu.utils.rng import DeterministicRandom

CAPACITY = 320  # 7/8 of it is 280 boundaries: 140 point writes
WINDOW = 1000


class Driven:
    """A resolver, the handles its engine gave out, the events it logged."""

    def __init__(self):
        KNOBS.set("CONFLICT_BACKEND", "device")
        KNOBS.set("CONFLICT_CPU_FALLBACK", "jax")
        KNOBS.set("CONFLICT_STATE_CAPACITY", CAPACITY)
        KNOBS.set("CONFLICT_BATCH_TXNS", 16)
        KNOBS.set("CONFLICT_BATCH_READS_PER_TXN", 2)
        KNOBS.set("CONFLICT_BATCH_WRITES_PER_TXN", 10)
        KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", WINDOW)
        self.loop = EventLoop()
        self.net = SimNetwork(self.loop, DeterministicRandom(32))
        self.proxy = self.net.new_process("proxy:0")
        self.resolver = Resolver(self.net.new_process("resolver:0"))
        self.handles = []
        engine = self.resolver.conflict_set
        detect_async = engine.detect_async

        def recording(txns, version):
            self.handles.append(detect_async(txns, version))
            return self.handles[-1]
        engine.detect_async = recording
        self.version = 0
        self.events: list[dict] = []

    def send(self, keys: list[bytes], version: int, per_txn: int = 10):
        """One batch of blind point writes, `per_txn` a transaction; the
        reply's verdicts, or the error it was answered with."""
        txns = [TxnConflictInfo(read_snapshot=version, write_ranges=[
            (k, k + b"\x00") for k in keys[i:i + per_txn]])
            for i in range(0, len(keys), per_txn)]
        req = ResolveTransactionBatchRequest(
            prev_version=self.version, version=version,
            last_receive_version=self.version, transactions=txns)
        self.version = version

        async def ask():
            try:
                return (await self.net.request(
                    self.proxy, Endpoint("resolver:0", Token.RESOLVER_RESOLVE),
                    req)).committed
            except FDBError as e:
                return e
        trace.set_sink(self.events.append)
        try:
            return self.loop.run_future(self.loop.spawn(ask()), max_time=60.0)
        finally:
            trace.set_sink(None)

    def metrics(self) -> dict:
        got = {}
        self.resolver._on_metrics(None, type("R", (), {
            "send": staticmethod(got.update)}))
        return got

    def near_full_events(self) -> list[dict]:
        return [e for e in self.events
                if e.get("Type") == "ResolverStateNearFull"]


def keys(lo: int, hi: int) -> list[bytes]:
    return [b"%016d" % i for i in range(lo, hi)]


def test_counters_are_the_sums_of_what_the_steps_left():
    d = Driven()
    m0 = d.metrics()
    assert [m0[k] for k in ("StateBoundariesSum", "StateCapacitySum",
                            "StateBoundariesPeak", "StateEvictedSum")] == [0] * 4
    # 14 batches of 40 fresh keys (80 boundaries), 400 versions apart: a
    # window of 1,000 versions holds three batches, the fourth evicts the
    # first; a batch of 17 transactions is cut into two chunks: two steps
    version = 0
    for b in range(14):
        version += 400
        assert d.send(keys(1000 * b, 1000 * b + 40), version) == [COMMITTED] * 4
    assert d.send(keys(50_000, 50_000 + 34), version + 400,
                  per_txn=2) == [COMMITTED] * 17
    steps = [s for h in d.handles for s in h.steps]
    assert len(steps) == 16 and len(d.handles[-1].steps) == 2
    m = d.metrics()
    assert m["StateBoundariesSum"] == sum(b for b, _ in steps)
    assert m["StateCapacitySum"] == CAPACITY * len(steps)
    assert m["StateBoundariesPeak"] == max(b for b, _ in steps)
    assert m["StateEvictedSum"] == sum(e for _, e in steps) > 800
    assert m["KernelDispatches"] - m0["KernelDispatches"] == len(steps)
    # the mean fill is what the rate and the window give, not what 594
    # distinct keys would
    fill = m["StateBoundariesSum"] / m["StateCapacitySum"]
    assert 0.5 < fill < 7 / 8 and m["StateBoundariesPeak"] < CAPACITY
    # the 5 s dump carries them with the role's other counters
    trace.set_sink(d.events.append)
    try:
        d.resolver.counters.trace(0.0)
    finally:
        trace.set_sink(None)
    dump = d.events[-1]
    assert dump["Type"] == "ResolverMetrics"
    assert dump["StateBoundariesPeak"] == m["StateBoundariesPeak"]
    assert dump["StateEvictedSum"] == m["StateEvictedSum"]


def test_near_full_is_said_once_per_crossing():
    d = Driven()
    # 60 + 60 keys: 241 boundaries, under 280; 30 more: 301, over
    d.send(keys(0, 60), 100)
    d.send(keys(100, 160), 200)
    assert d.near_full_events() == []
    d.send(keys(200, 230), 300)
    d.send(keys(230, 235), 400)  # still over: nothing more is said
    said = d.near_full_events()
    assert len(said) == 1, said
    assert said[0]["Capacity"] == CAPACITY and said[0]["Version"] == 300
    assert 8 * said[0]["Boundaries"] > 7 * CAPACITY
    assert said[0]["Boundaries"] == d.handles[2].steps[0][0]
    # a window later the old rows are gone; then it fills again
    d.send(keys(300, 310), 2000)
    assert d.handles[-1].steps[0][0] < 100
    d.send(keys(400, 460), 2100)
    d.send(keys(500, 560), 2200)
    assert len(d.near_full_events()) == 1
    d.send(keys(600, 615), 2300)
    assert len(d.near_full_events()) == 2
    assert d.near_full_events()[1]["Version"] == 2300
    assert d.metrics()["Poisoned"] is False


@pytest.mark.parametrize("later_batches", [1, 3])
def test_an_overflow_poisons_and_answers_every_later_batch_with_the_error(
        later_batches):
    d = Driven()
    assert d.send(keys(0, 100), 100) == [COMMITTED] * 10
    before = d.metrics()
    err = d.send(keys(1000, 1100), 200)  # 401 boundaries in 320
    assert isinstance(err, FDBError) and err.name == "internal_error"
    assert "capacity exceeded" in str(err)
    poisoned = [e for e in d.events if e.get("Type") == "ResolverPoisoned"]
    assert len(poisoned) == 1 and poisoned[0]["Version"] == 200
    for i in range(later_batches):
        again = d.send(keys(5000 + i, 5001 + i), 300 + 100 * i)
        assert isinstance(again, FDBError) and again.name == "internal_error"
    m = d.metrics()
    assert m["Poisoned"] is True
    assert len([e for e in d.events
                if e.get("Type") == "ResolverPoisoned"]) == 1
    # a step that overflowed is not a sample of the state
    for k in ("StateBoundariesSum", "StateCapacitySum", "StateEvictedSum",
              "StateBoundariesPeak"):
        assert m[k] == before[k], k
