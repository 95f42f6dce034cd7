"""The yardstick's arithmetic against numbers worked by hand: the traffic
generator, the percentile over the workers' raw samples, the roofline's byte
count, the interval arithmetic of the trace reduction, and the reference on
hand-built histories."""

import random
import zlib

import numpy as np
import pytest

import kernel_cost
import traffic as tr
from actor import ACKNOWLEDGED, FAILED, LOG_DTYPE
from reference import LIMITS, check_history, judge
from readers.latency import NEVER_MS, percentile, read as read_latency

FDB = {"records": 500, "key": {"kind": "decimal", "bytes": 16},
       "value": {"kind": "bytes", "min": 8, "max": 100}}
YCSB = {"records": 500, "key": {"kind": "ycsb_hashed", "prefix": "user"},
        "value": {"kind": "record", "fields": 10, "field_bytes": 100}}
WRITE10 = {"ops_per_txn": 10, "operations": {"set": 1.0},
           "request_distribution": {"kind": "uniform"}}
YCSB_F = {"ops_per_txn": 1, "operations": {"read": 0.5, "rmw": 0.5},
          "request_distribution": {"kind": "scrambled_zipfian",
                                   "constant": 0.99}}


# ------------------------------------------------------------- the traffic

def _java_fnv(val: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        octet = val & 0xFF
        val >>= 8
        h ^= octet
        h = (h * 0x100000001B3) % (1 << 64)
    signed = h - (1 << 64) if h >= 1 << 63 else h
    return abs(signed)


def test_fnv_hash_is_ycsbs_and_the_array_form_agrees():
    vals = [0, 1, 2, 255, 256, 99_999, 10_000_000_000 - 1]
    assert [tr.fnvhash64(v) for v in vals] == [_java_fnv(v) for v in vals]
    assert tr.fnvhash64_array(np.array(vals)).tolist() == [
        _java_fnv(v) for v in vals]


def test_keys_and_values_have_the_sources_shapes():
    fdb = tr.Data(FDB, seed=7)
    assert fdb.keys[0] == b"0000000000000000" and len(fdb.keys[499]) == 16
    lens = [len(v) for v in fdb.initial_values()]
    assert min(lens) >= 8 and max(lens) <= 100 and len(set(lens)) > 50
    ycsb = tr.Data(YCSB, seed=7)
    assert ycsb.keys[3] == b"user%d" % _java_fnv(3)
    record = ycsb.initial_values()[0]
    # ten fields of 100 bytes, each framed by index (1) and length (2)
    assert len(record) == 10 * (100 + tr.FRAME) == 1030
    assert record[0] == 0 and record[1:3] == (100).to_bytes(2, "big")
    changed = tr.replace_field(record, 4, b"x" * 100)
    assert changed[4 * 103 + 3:5 * 103] == b"x" * 100
    assert changed[:4 * 103 + 3] == record[:4 * 103 + 3]
    assert changed[5 * 103:] == record[5 * 103:]


@pytest.mark.parametrize("data,mix", [(FDB, WRITE10), (YCSB, YCSB_F)])
def test_the_same_seed_gives_the_same_data_and_plans(data, mix):
    def draw(seed):
        d = tr.Data(data, seed)
        t = tr.Traffic(mix, d)
        rng = t.actor_rng(seed, worker=2, actor=5)
        return d.initial_values()[:20], [t.plan(rng) for _ in range(50)], \
            t.make_pool(seed)[:64]
    big = 3_000_000_019  # more than 32 signed bits hold
    assert draw(big) == draw(big)
    assert draw(big) != draw(big + 1)
    d = tr.Data(data, big)
    t = tr.Traffic(mix, d)
    a = t.plan(t.actor_rng(big, 0, 0))
    b = t.plan(t.actor_rng(big, 0, 1))
    c = t.plan(t.actor_rng(big, 1, 0))
    assert a != b and a != c, "actors and workers draw streams of their own"


def test_zipfian_first_ranks_match_the_closed_form():
    theta = 0.99
    # the closed form on a size a sum can reach: P(rank r) = (r+1)^-theta/zeta
    n = 1000
    zetan = tr.zeta(n, theta)
    assert zetan == pytest.approx(sum(1 / i ** theta for i in range(1, n + 1)))
    z = tr.Zipfian(n, theta, zetan)
    rng = random.Random(5)
    draws = 400_000
    counts = np.bincount([z.rank(rng.random()) for _ in range(draws)],
                         minlength=n)
    for r in (0, 1):  # exact by construction in Gray's method
        want = (r + 1) ** -theta / zetan
        assert counts[r] / draws == pytest.approx(want, rel=0.02)
    # from rank 2 on Gray's method approximates, and at a constant this near
    # 1 it overshoots the next ranks by up to a fifth (YCSB's own does too)
    for r in (2, 3, 4, 9):
        want = (r + 1) ** -theta / zetan
        assert counts[r] / draws == pytest.approx(want, rel=0.25)
    assert all(counts[r] > counts[r + 1] for r in range(8))
    assert counts.max() == counts[0] and z.rank(0.999999) < n
    # YCSB's own constants: the hottest item draws 1/zeta(10^10) = 3.8%
    y = tr.Zipfian(tr.YCSB_ITEM_COUNT, theta, tr.YCSB_ZETAN)
    assert y.rank(0.999 / tr.YCSB_ZETAN) == 0
    assert y.rank(1.001 / tr.YCSB_ZETAN) == 1
    assert y.rank((1 + 0.5 ** theta) * 1.001 / tr.YCSB_ZETAN) >= 2
    assert y.rank(1.0 - 1e-12) < tr.YCSB_ITEM_COUNT


def test_the_mixes_shares_and_spread_over_the_shards():
    d = tr.Data(dict(YCSB, records=20_000), seed=11)
    t = tr.Traffic(YCSB_F, d)
    rng = t.actor_rng(11, 0, 0)
    plans = [t.plan(rng)[0] for _ in range(40_000)]
    share_rmw = sum(op == tr.RMW for op, *_ in plans) / len(plans)
    assert share_rmw == pytest.approx(0.5, abs=0.01)
    hits = np.bincount([k for _op, k, *_ in plans], minlength=d.count)
    assert hits.max() / len(plans) == pytest.approx(1 / tr.YCSB_ZETAN,
                                                    rel=0.1)
    cut = d.cut_keys(2)[0]
    upper = sum(hits[i] for i, k in enumerate(d.keys) if k >= cut)
    assert 0.3 < upper / len(plans) < 0.7, "hot records lie on both shards"
    w = tr.Traffic(WRITE10, tr.Data(FDB, seed=11))
    plan = w.plan(w.actor_rng(11, 0, 0))
    assert len(plan) == 10 and {op for op, *_ in plan} == {tr.SET}
    assert all(8 <= b <= 100 for _op, _k, _a, b in plan)


# ---------------------------------------------- percentiles over raw samples

def test_percentile_is_taken_over_the_merged_raw_samples():
    fast = [1.0] * 95 + [2.0] * 5          # a worker with a short tail
    slow = [1.0] * 10 + [50.0] * 10        # a smaller, slower worker
    merged = percentile(fast + slow, 0.95)
    assert merged == 50.0
    per_worker = [percentile(fast, 0.95), percentile(slow, 0.95)]
    assert per_worker == [1.0, 50.0]
    assert merged != sum(per_worker) / 2
    assert percentile(list(range(1, 101)), 0.95) == 95  # nearest rank
    assert percentile([3.0], 0.95) == 3.0
    with pytest.raises(ValueError):
        percentile([], 0.95)
    # the metric's reader: nothing to read is nothing, and a tail that falls
    # on a failed transaction is printed as never
    ctx = {"samples": {"commit": fast + slow, "read": []}}
    assert read_latency(ctx, kind="commit", q=0.95) == 50.0
    assert read_latency(ctx, kind="read", q=0.95) is None
    ctx["samples"]["commit"] = [1.0] * 9 + [float("inf")]
    assert read_latency(ctx, kind="commit", q=0.5) == 1.0
    assert read_latency(ctx, kind="commit", q=0.95) == NEVER_MS


# ------------------------------------------------------ the roofline's bytes

def test_conflict_step_bytes_against_hand_worked_numbers():
    # 2^18 boundaries, 256 txns x 10+10, 24-byte keys: 7 limbs, 19 levels
    #   state  4*262144*(7+1+19) + 9            = 28,311,561
    #   batch  4*7*2*5120 + 4*5120 + 1280 + 5   =    308,485
    #   out    4*256                            =      1,024
    assert kernel_cost.conflict_step_bytes(
        capacity=262144, txns=256, reads=2560, writes=2560,
        key_bytes=24) == 2 * 28_311_561 + 308_485 + 1_024 == 56_932_631
    # 8192 boundaries, 64 txns x 2+2: 7 limbs, 14 levels
    #   state 4*8192*22 + 9 = 720,905; batch 14,336 + 1,024 + 320 + 5
    assert kernel_cost.conflict_step_bytes(
        capacity=8192, txns=64, reads=128, writes=128,
        key_bytes=24) == 2 * 720_905 + 15_685 + 256 == 1_457_751
    shapes = kernel_cost.conflict_shapes({"knobs": {
        "CONFLICT_STATE_CAPACITY": 262144, "CONFLICT_BATCH_TXNS": 256,
        "CONFLICT_BATCH_READS_PER_TXN": 10,
        "CONFLICT_BATCH_WRITES_PER_TXN": 10}})
    assert shapes == {"capacity": 262144, "txns": 256, "reads": 2560,
                      "writes": 2560, "key_bytes": 24}


def test_an_unknown_device_kind_is_an_error():
    from readers import roofline
    assert roofline.peak_bytes_per_s("TPU v5 lite") == 819e9
    for kind in ("TPU v9", "cpu", "source", ""):
        with pytest.raises(KeyError):
            roofline.peak_bytes_per_s(kind)


# ------------------------------------------- the reference, by hand

class Scripted:
    """A traffic whose plans are written out: plans[(worker, actor)] is the
    list an actor draws from, in order."""

    def __init__(self, plans):
        self.plans = plans

    def actor_rng(self, _seed, worker, actor):
        return iter(self.plans[(worker, actor)])

    def plan(self, rng):
        return next(rng)

    def fresh(self, _pool, _op, a, _b):
        return b"fresh%d" % a

    def modify(self, old, fresh, _b):
        return (old or b"") + b"+" + fresh


def _crc(*values):
    c = 0
    for v in values:
        c = zlib.crc32(v, c)
    return c


def _log(rows):
    return np.array(rows, dtype=LOG_DTYPE)


def _row(actor, seq, rv, cv, crc=0, status=ACKNOWLEDGED, writes=1):
    return (actor, seq, 0.0, 1.0, 1, 0, rv, cv, crc, status, writes)


INITIAL = [b"a0", b"b0", b"c0"]


def _judge(plans, logs, readback):
    numbers, notes = check_history(Scripted(plans), 0, b"", INITIAL, logs,
                                   readback)
    return numbers, notes, judge(numbers, LIMITS)[0]


def test_disjoint_pair_both_committed_is_sound():
    plans = {(0, 0): [[(tr.RMW, 0, 1, 0)]], (0, 1): [[(tr.RMW, 1, 2, 0)]]}
    logs = {0: _log([_row(0, 0, rv=100, cv=200, crc=_crc(b"a0")),
                     _row(1, 0, rv=100, cv=200, crc=_crc(b"b0"))])}
    numbers, _notes, ok = _judge(plans, logs, {
        0: b"a0+fresh1", 1: b"b0+fresh2", 2: b"c0"})
    assert ok and not any(numbers.values())


def test_conflicting_pair_both_committed_is_a_violation():
    # both read record 0 at version 100; one commits at 200, the other at
    # 300 without having seen 200's write: the second had to be refused
    plans = {(0, 0): [[(tr.RMW, 0, 1, 0)]], (0, 1): [[(tr.RMW, 0, 2, 0)]]}
    logs = {0: _log([_row(0, 0, rv=100, cv=200, crc=_crc(b"a0")),
                     _row(1, 0, rv=100, cv=300, crc=_crc(b"a0"))])}
    numbers, _notes, ok = _judge(plans, logs, {
        0: b"a0+fresh2", 1: b"b0", 2: b"c0"})
    assert not ok and numbers["conflict_violations"] == 1
    # the same pair in one commit batch: one of the two read a stale record
    logs = {0: _log([_row(0, 0, rv=100, cv=200, crc=_crc(b"a0")),
                     _row(1, 0, rv=100, cv=200, crc=_crc(b"a0"))])}
    numbers, _notes, ok = _judge(plans, logs, None)
    assert not ok and numbers["conflict_violations"] == 1
    # refused and retried after the first one's commit: sound
    logs = {0: _log([_row(0, 0, rv=100, cv=200, crc=_crc(b"a0")),
                     _row(1, 0, rv=250, cv=300, crc=_crc(b"a0+fresh1"))])}
    numbers, _notes, ok = _judge(plans, logs, {
        0: b"a0+fresh1+fresh2", 1: b"b0", 2: b"c0"})
    assert ok and not any(numbers.values())


def test_a_stale_or_altered_read_and_a_lost_write_are_counted():
    plans = {(0, 0): [[(tr.SET, 2, 7, 0)]],
             (0, 1): [[(tr.READ, 2, 0, 0)], [(tr.READ, 2, 0, 0)]]}
    readback = {0: b"a0", 1: b"b0", 2: b"fresh7"}
    sound = {0: _log([_row(0, 0, rv=0, cv=200),
                      _row(1, 0, rv=150, cv=0, crc=_crc(b"c0"), writes=0),
                      _row(1, 1, rv=200, cv=0, crc=_crc(b"fresh7"),
                           writes=0)])}
    numbers, notes, ok = _judge(plans, sound, readback)
    assert ok and notes["reads_compared"] == 2
    stale = {0: sound[0].copy()}
    stale[0]["crc"][2] = _crc(b"c0")  # at version 200 the write is visible
    assert _judge(plans, stale, readback)[0]["read_mismatches"] == 1
    altered = {0: sound[0].copy()}
    altered[0]["crc"][1] = _crc(b"c1")
    assert _judge(plans, altered, readback)[0]["read_mismatches"] == 1
    lost = dict(readback)
    lost[2] = b"c0"  # acknowledged at 200, not in storage
    numbers, _notes, ok = _judge(plans, sound, lost)
    assert not ok and numbers["readback_mismatches"] == 1
    stray = dict(readback)
    stray[-1] = b"a key nobody wrote"
    assert _judge(plans, sound, stray)[0]["readback_mismatches"] == 1


def test_a_read_of_the_plans_own_write_is_taken_from_the_plan():
    # read-your-writes: the client answers `get` of a record the transaction
    # has set from its write map, at no read version
    plans = {(0, 0): [[(tr.SET, 2, 7, 0), (tr.READ, 2, 0, 0),
                       (tr.READ, 1, 0, 0)],
                      [(tr.RMW, 0, 3, 0), (tr.READ, 0, 0, 0)]]}
    readback = {0: b"a0+fresh3", 1: b"b0", 2: b"fresh7"}
    sound = {0: _log([_row(0, 0, rv=100, cv=200, crc=_crc(b"fresh7", b"b0")),
                      _row(0, 1, rv=250, cv=300,
                           crc=_crc(b"a0", b"a0+fresh3"))])}
    numbers, notes, ok = _judge(plans, sound, readback)
    assert ok and not any(numbers.values()) and notes["reads_compared"] == 2
    # a broken system: the own-write read answered from storage
    for at, from_storage in ((0, _crc(b"c0", b"b0")), (1, _crc(b"a0", b"a0"))):
        broken = {0: sound[0].copy()}
        broken[0]["crc"][at] = from_storage
        numbers, _notes, ok = _judge(plans, broken, readback)
        assert not ok and numbers["read_mismatches"] == 1


def test_a_read_of_the_plans_own_write_is_no_read_of_the_database():
    # record 2 is written at 150, between the second one's read and commit
    # versions; after its own set the second one's read of 2 adds no read
    # conflict, so both may commit. Before its own set, it does.
    logs = {0: _log([_row(0, 0, rv=0, cv=150),
                     _row(1, 0, rv=100, cv=200)])}
    after = {(0, 0): [[(tr.SET, 2, 1, 0)]],
             (0, 1): [[(tr.SET, 2, 7, 0), (tr.READ, 2, 0, 0),
                       (tr.RMW, 2, 8, 0)]]}
    logs[0]["crc"][1] = _crc(b"fresh7", b"fresh7")
    numbers, _notes, ok = _judge(after, logs, {0: b"a0", 1: b"b0",
                                               2: b"fresh7+fresh8"})
    assert ok and not any(numbers.values())
    before = {(0, 0): after[(0, 0)],
              (0, 1): [[(tr.READ, 2, 0, 0), (tr.SET, 2, 7, 0)]]}
    logs[0]["crc"][1] = _crc(b"c0")
    numbers, _notes, ok = _judge(before, logs, {0: b"a0", 1: b"b0",
                                                2: b"fresh7"})
    assert not ok and numbers["conflict_violations"] == 1
    assert numbers["read_mismatches"] == 0  # it read what stood at 100


def test_of_two_writes_of_one_record_in_one_plan_the_later_stands():
    plans = {(0, 0): [[(tr.SET, 1, 1, 0), (tr.SET, 1, 2, 0),
                       (tr.SET, 0, 3, 0), (tr.RMW, 0, 4, 0)]]}
    logs = {0: _log([_row(0, 0, rv=100, cv=200, crc=_crc(b"fresh3"))])}
    numbers, notes, ok = _judge(plans, logs, {0: b"fresh3+fresh4",
                                              1: b"fresh2", 2: b"c0"})
    assert ok and notes["same_version_ties"] == 0
    numbers, _notes, ok = _judge(plans, logs, {0: b"fresh3+fresh4",
                                               1: b"fresh1", 2: b"c0"})
    assert not ok and numbers["readback_mismatches"] == 1
    # over a base that a tie of two transactions left open, what the plan
    # then reads of its own write is not judged, and either end is accepted
    plans = {(0, 0): [[(tr.SET, 1, 1, 0)]], (0, 1): [[(tr.SET, 1, 2, 0)]],
             (0, 2): [[(tr.RMW, 1, 3, 0), (tr.READ, 1, 0, 0)]]}
    logs = {0: _log([_row(0, 0, rv=0, cv=200), _row(1, 0, rv=0, cv=200),
                     _row(2, 0, rv=250, cv=300, crc=12345)])}
    for last in (b"fresh1+fresh3", b"fresh2+fresh3"):
        numbers, notes, ok = _judge(plans, logs, {0: b"a0", 1: last,
                                                  2: b"c0"})
        assert ok and notes["ambiguous_reads"] == 1
    assert not _judge(plans, logs, {0: b"a0", 1: b"fresh1", 2: b"c0"})[2]


def test_blind_writes_of_one_batch_may_land_either_way():
    plans = {(0, 0): [[(tr.SET, 1, 1, 0)]], (0, 1): [[(tr.SET, 1, 2, 0)]]}
    logs = {0: _log([_row(0, 0, rv=0, cv=200), _row(1, 0, rv=0, cv=200)])}
    for last in (b"fresh1", b"fresh2"):
        numbers, notes, ok = _judge(plans, logs, {0: b"a0", 1: last, 2: b"c0"})
        assert ok and notes["same_version_ties"] == 1
    assert not _judge(plans, logs, {0: b"a0", 1: b"b0", 2: b"c0"})[2]


def test_a_failed_transaction_is_left_out_not_judged():
    plans = {(0, 0): [[(tr.SET, 1, 1, 0)], [(tr.SET, 0, 2, 0)]]}
    logs = {0: _log([_row(0, 0, rv=0, cv=0, status=FAILED),
                     _row(0, 1, rv=0, cv=300)])}
    # nobody knows whether it committed: its record may read either way
    for value in (b"b0", b"fresh1"):
        numbers, notes, ok = _judge(plans, logs, {0: b"fresh2", 1: value,
                                                   2: b"c0"})
        assert ok and notes["failed_txns"] == 1
        assert notes["tainted_records"] == 1
    assert not _judge(plans, logs, {0: b"a0", 1: b"b0", 2: b"c0"})[2]
    with pytest.raises(ValueError, match="lacks transaction"):
        _judge(plans, {0: logs[0][1:]}, None)
