"""resolutionBalancing of the key-partitioned engine on whole keys.

The partition's cuts are full keys that the engine plans itself from what
it is offered (parallel/sharded_conflict.py). Upstream's benchmark keys are
`b"%016d"` (all begin `00000000000`) and YCSB's are `user<fnv64>`: every key
of either shares its first bytes, which cuts on a 4-byte prefix cannot part.
These tests start cold, on the default equal cuts, with every key on one
shard:

(a) the engine finds the keys by itself before a shard overflows;
(b) across moves at full-key cuts the verdicts are those of per-shard
    clipped oracles combined by min, with a move modelled as what its
    docstring says: kept subranges exact, acquired ones filled at the
    move's version;
(c) a move adds conflicts, never a commit the one-device engine refuses;
(d) the planner alone;
(e) the served path under the simulator with the benchmark's keys.
"""

from __future__ import annotations

import numpy as np
import pytest

from foundationdb_tpu.ops.batch import COMMITTED, CONFLICT, TxnConflictInfo
from foundationdb_tpu.ops.conflict import DeviceConflictSet
from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
from foundationdb_tpu.parallel.sharded_conflict import (
    ShardedDeviceConflictSet, make_resolver_mesh, plan_cuts,
    shard_cut_bytes_range)
from foundationdb_tpu.utils import keys as keylib
from foundationdb_tpu.utils.knobs import KNOBS
from foundationdb_tpu.utils.rng import DeterministicRandom

from chip_smoke import _move_oracles  # a move, on the per-shard oracles
from test_sharded import _SafetyTracker, _sharded_oracle_detect

N_KEYS = 1500
SHAPE = dict(capacity=1024, txns=16, reads_per_txn=10, writes_per_txn=10)


def _decimal_keys(n=N_KEYS):
    return [b"%016d" % i for i in range(n)]


def _ycsb_keys(n=N_KEYS):
    """`user` + FNV-64 of the record number, as YCSB's CoreWorkload hashes
    (benchmark/traffic.py makes the benchmark's the same way)."""
    out = []
    for i in range(n):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (i & 0xFF)) * 0x100000001B3) & ((1 << 64) - 1)
            i >>= 8
        out.append(b"user%d" % h)
    return sorted(out)


def _point(k):
    return (k, k + b"\x00")


def _small_balance_knobs(check_batches=4, min_samples=64):
    KNOBS.set("RESOLUTION_BALANCE_CHECK_BATCHES", check_batches)
    KNOBS.set("RESOLUTION_BALANCE_MIN_SAMPLES", min_samples)


# -- (a) ---------------------------------------------------------------------

@pytest.mark.parametrize("make_keys", [_decimal_keys, _ycsb_keys],
                         ids=["decimal16", "ycsb_user_hash"])
def test_cold_start_finds_keys_that_share_a_prefix(make_keys):
    """The load walks the keys in ascending order, then uniform traffic:
    3,000 boundaries cannot lie on one shard of 1,024 (the parent ends
    here with `conflict state capacity exceeded`), so the cuts have to move
    into the keys, and in the end every shard holds some."""
    _small_balance_knobs(check_batches=8, min_samples=256)
    cs = ShardedDeviceConflictSet(mesh=make_resolver_mesh(4), **SHAPE)
    keys = make_keys()
    assert len({k[:4] for k in keys}) == 1  # one 4-byte prefix for all
    cold = list(cs.cut_bytes)
    tracker = _SafetyTracker()
    version = 100
    for i in range(0, N_KEYS, 160):  # 16 transactions of 10 sets a batch
        txns = [TxnConflictInfo(read_snapshot=version,
                                write_ranges=[_point(k)
                                              for k in keys[j:j + 10]])
                for j in range(i, min(i + 160, N_KEYS), 10)]
        version += 10
        tracker.check_and_apply(txns, cs.detect(txns, version), version)
    rng = np.random.RandomState(5)
    moves_after_load = cs.rebalances
    assert moves_after_load >= 1, "the load never moved a cut"
    offered = fullest = 0
    for b in range(96):
        txns = []
        for _ in range(16):
            picked = [keys[x] for x in rng.randint(0, N_KEYS, 10)]
            txns.append(TxnConflictInfo(
                read_snapshot=version - int(rng.randint(0, 30)),
                read_ranges=[_point(picked[0])],
                write_ranges=[_point(k) for k in picked]))
        version += 10
        tracker.check_and_apply(txns, cs.detect(txns, version), version)
        if b == 47:  # the partition has had its time to settle
            offered, fullest = cs.ranges_offered, cs.ranges_fullest
            settled = cs.rebalances
    nb = np.asarray(cs._state["nb"])
    assert (nb > 1).all(), f"a shard holds no keys: {nb}"
    assert cs.cut_bytes != cold
    assert all(c > keys[0] and c <= keys[-1] for c in cs.cut_bytes[1:])
    assert cs.rebalances == settled, "the cuts still move under even load"
    share = (cs.ranges_fullest - fullest) / (cs.ranges_offered - offered)
    assert share <= 0.34, f"the busiest shard is offered {share:.0%}"
    assert cs.fill_fullest <= SHAPE["capacity"]


# -- (b), (c) ----------------------------------------------------------------

def _stream(seed, keys, n_batches, version, blind=False):
    rng = DeterministicRandom(seed)

    def some_range():
        i = rng.randint(0, len(keys) - 1)
        if rng.randint(0, 3) == 0:  # a range over a few neighbours
            return (keys[i], keys[min(i + rng.randint(1, 4), len(keys) - 1)]
                    + b"\x00")
        return _point(keys[i])

    for _ in range(n_batches):
        txns = []
        for _ in range(12):
            reader = rng.randint(0, 2) == 0
            txns.append(TxnConflictInfo(
                read_snapshot=version - rng.randint(0, 40),
                read_ranges=([some_range() for _ in range(rng.randint(1, 3))]
                             if reader or not blind else []),
                write_ranges=([some_range() for _ in range(rng.randint(1, 3))]
                              if not reader or not blind else [])))
        version += rng.randint(5, 25)
        yield txns, version


# cuts the 4-byte planners could not make: whole 16-byte keys; the 11 zeros
# the records share, which sort before every record; a 14-byte cut, which
# sorts between records 99 and 100; a record's successor; records themselves
MOVES = [
    [b"", b"0000000000000050", b"0000000000000100", b"0000000000000150"],
    [b"", b"00000000000", b"00000000000001", b"0000000000000120\x00"],
    [b"", b"0000000000000007", b"0000000000000008", b"0000000000000199"],
]


def test_parity_with_clipped_oracles_across_whole_key_moves():
    keys = _decimal_keys(200)
    mesh = make_resolver_mesh(4)
    cs = ShardedDeviceConflictSet(mesh=mesh, capacity=1024, txns=16,
                                  reads_per_txn=4, writes_per_txn=4)
    cuts = list(cs.cut_bytes)
    oracles = [OracleConflictSet() for _ in cuts]
    version = 100
    counts = {COMMITTED: 0, CONFLICT: 0}
    for step, new_cuts in enumerate([None] + MOVES):
        if new_cuts is not None:
            assert new_cuts == sorted(set(new_cuts))
            version += 3  # a move has a version of its own
            cs.rebalance_cuts(new_cuts, version)
            _move_oracles(oracles, cuts, new_cuts, version)
            cuts = new_cuts
            assert cs.cut_bytes == new_cuts
            lo = np.asarray(cs._state["lo"])
            assert [keylib.decode_key(r) for r in lo] == new_cuts
        for txns, version in _stream(40 + step, keys, 8, version):
            got = cs.detect(txns, version)
            assert got == _sharded_oracle_detect(oracles, cuts, txns, version)
            for g in got:
                counts[g] = counts.get(g, 0) + 1
    assert cs.rebalances == len(MOVES)  # none but the ones made here
    assert counts[COMMITTED] > 50 and counts[CONFLICT] > 50, counts


def test_moves_leave_every_shard_distinct_and_padded():
    """What the step's built key groups lean on, shard by shard on a
    four-device mesh: after steps and after every move of the cuts
    (rebalance_cuts rewrites the keys on the host) a shard's live keys are
    strictly increasing and the slots behind them hold MAX_LIMBS."""
    from test_conflict import assert_state_keys_distinct
    keys = _decimal_keys(200)
    cs = ShardedDeviceConflictSet(mesh=make_resolver_mesh(4), capacity=1024,
                                  txns=16, reads_per_txn=4, writes_per_txn=4)

    def check():
        for bkeys, nb in zip(np.asarray(cs._state["bkeys"]),
                             np.asarray(cs._state["nb"])):
            assert_state_keys_distinct(bkeys, nb)

    version = 100
    check()
    for step, new_cuts in enumerate(MOVES + MOVES[:1]):
        for txns, version in _stream(90 + step, keys, 4, version):
            cs.detect(txns, version)
        check()
        version += 3
        cs.rebalance_cuts(new_cuts, version)
        check()
    assert cs.rebalances >= len(MOVES) + 1


def test_a_move_adds_conflicts_and_never_a_commit():
    """Readers and blind writers: every writer commits in both engines, so
    their histories are the same writes, and whatever the key-partitioned
    engine lets a reader commit the one-device engine must let commit too —
    across moves the engine makes itself and moves made here."""
    _small_balance_knobs()
    keys = _decimal_keys(400)
    cs = ShardedDeviceConflictSet(mesh=make_resolver_mesh(4), capacity=1024,
                                  txns=16, reads_per_txn=4, writes_per_txn=4)
    single = DeviceConflictSet(capacity=4096, txns=16, reads_per_txn=4,
                               writes_per_txn=4)
    version, extra, agreed = 100, 0, 0
    for step in range(4):
        for txns, version in _stream(70 + step, keys, 10, version,
                                     blind=True):
            got = cs.detect(txns, version)
            want = single.detect(txns, version)
            for t, g, w in zip(txns, got, want):
                if not t.read_ranges:
                    assert g == w == COMMITTED
                elif g == COMMITTED:
                    assert w == COMMITTED, "a false commit"
                    agreed += 1
                else:
                    extra += w == COMMITTED
        version += 3
        cs.rebalance_cuts(MOVES[step % len(MOVES)], version)
    assert cs.rebalances > 4, "the engine made no move of its own"
    assert extra > 0, "no move cost a conflict: the moves were not felt"
    assert agreed > 20


# -- (d) ---------------------------------------------------------------------

def _rows(keys):
    return np.stack([keylib.encode_key(k) for k in keys])


def test_planner_cuts_at_quantiles_of_whole_keys():
    keys = _decimal_keys(1000)
    rng = np.random.RandomState(3)
    sample = _rows([keys[i] for i in rng.permutation(1000)])
    cuts = plan_cuts(sample, None, 4)
    assert cuts == [b"", keys[250], keys[500], keys[750]]
    # mass, not count: the first hundred keys carry half of it
    weights = np.where(np.arange(1000) < 100, 9.0, 1.0)
    cuts = plan_cuts(_rows(keys), weights, 2)
    assert cuts == [b"", keys[100]]


@pytest.mark.parametrize("keys,n,want", [
    # two keys that differ only in their last byte are split there
    ([b"0000000000000041"] * 50 + [b"0000000000000042"] * 50, 2,
     [b"", b"0000000000000042"]),
    # a key and its successor
    ([b"user1"] * 5 + [b"user1\x00"] * 5, 2, [b"", b"user1\x00"]),
    # fewer keys than shards: each key a shard, the spare cut after the last
    ([b"a", b"b", b"c"], 4, [b"", b"b", b"c", b"c\x00"]),
    # all the mass on one key: a hot key, not a matter for a cut
    ([b"0000000000000041"] * 100, 4, None),
    ([], 4, None),
])
def test_planner_edge_cases(keys, n, want):
    rows = _rows(keys) if keys else np.zeros((0, keylib.NUM_LIMBS), np.uint32)
    assert plan_cuts(rows, None, n) == want


def test_cold_cuts_of_an_owned_range_are_whole_keys():
    """The inner split of an outer resolver partition: a range that shares
    its first four bytes is cut at the next width, not declined."""
    assert shard_cut_bytes_range(4) == [
        b"", b"\x40\x00\x00\x00", b"\x80\x00\x00\x00", b"\xc0\x00\x00\x00"]
    cuts = shard_cut_bytes_range(4, b"user1", b"user2")
    assert cuts[0] == b"" and cuts == sorted(cuts)
    assert all(b"user1" < c < b"user2" for c in cuts[1:])
    assert len(set(cuts)) == 4


def test_one_hot_key_is_left_where_it_is():
    """Skew that no cut can mend: the look declines and moves nothing."""
    _small_balance_knobs()
    cs = ShardedDeviceConflictSet(mesh=make_resolver_mesh(4), **SHAPE)
    hot = _point(b"0000000000000041")
    version = 100
    for _ in range(12):
        txns = [TxnConflictInfo(read_snapshot=version, write_ranges=[hot] * 10)
                for _ in range(16)]
        version += 10
        assert cs.detect(txns, version) == [COMMITTED] * 16
    assert cs.rebalances == 0
    assert cs.ranges_fullest == cs.ranges_offered > 0


# -- (e) ---------------------------------------------------------------------

def test_served_path_with_benchmark_keys_on_the_sharded_backend():
    """Through the client API and the role pipeline of the simulated
    cluster with CONFLICT_BACKEND=sharded on four host devices: the load as
    the benchmark makes it, acknowledged writes read back, and of a
    conflicting pair one is refused — after the cuts moved into the keys."""
    from foundationdb_tpu.server.cluster import SimCluster
    from foundationdb_tpu.utils.errors import FDBError

    _small_balance_knobs()
    KNOBS.set("CONFLICT_BACKEND", "sharded")
    KNOBS.set("CONFLICT_NUM_SHARDS", 4)
    KNOBS.set("CONFLICT_CPU_FALLBACK", "jax")
    KNOBS.set("CONFLICT_STATE_CAPACITY", 256)
    KNOBS.set("CONFLICT_BATCH_TXNS", 16)
    KNOBS.set("CONFLICT_BATCH_READS_PER_TXN", 10)
    KNOBS.set("CONFLICT_BATCH_WRITES_PER_TXN", 10)
    c = SimCluster(seed=28)
    db = c.database()
    engine = c.resolvers[0].conflict_set
    assert engine.backend_label == "cpux4"
    keys = _decimal_keys(400)  # 800 boundaries: three shards' worth
    outcome = {}

    async def drive():
        for i in range(0, len(keys), 10):
            async def body(tr, i=i):
                for k in keys[i:i + 10]:
                    tr.set(k, b"v" + k[-4:])
            await db.transact(body)
        tr = db.create_transaction()
        outcome["read"] = [await tr.get(k) for k in keys[::37]]
        t1, t2 = db.create_transaction(), db.create_transaction()
        await t1.get(keys[123])
        await t2.get(keys[123])
        t1.set(keys[123], b"t1")
        t2.set(keys[123], b"t2")
        await t1.commit()
        try:
            await t2.commit()
            outcome["t2"] = "committed"
        except FDBError as e:
            outcome["t2"] = e.name
        tr = db.create_transaction()
        outcome["after"] = await tr.get(keys[123])

    c.run(c.loop.spawn(drive()))
    assert outcome["read"] == [b"v" + k[-4:] for k in keys[::37]]
    assert outcome["t2"] == "not_committed"
    assert outcome["after"] == b"t1"
    assert c.resolvers[0]._poisoned is None
    assert engine.rebalances >= 1 and engine.cut_bytes[1] > keys[0]
    metrics = {}
    c.resolvers[0]._on_metrics(None, type("R", (), {
        "send": staticmethod(metrics.update)}))
    assert metrics["CutRebalances"] == engine.rebalances
    assert metrics["ShardRangesOffered"] >= metrics["ShardRangesFullest"] > 0
    assert 1 < metrics["ShardBoundariesFullest"] <= 256
