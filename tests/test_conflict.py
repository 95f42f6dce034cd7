"""Conflict engine tests: device kernel vs CPU oracle — identical decisions.

This is the oracle-test pattern the reference uses for its own conflict engine
(SkipList.cpp:1394 miniConflictSetTest cross-checks the bitmask against a
naive implementation): generate randomized batches, run both engines, assert
byte-identical abort decisions.
"""

import dataclasses

import numpy as np
import pytest

from foundationdb_tpu.ops.batch import COMMITTED, CONFLICT, TOO_OLD, TxnConflictInfo
from foundationdb_tpu.ops.conflict import DeviceConflictSet
from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
from foundationdb_tpu.utils.knobs import KNOBS
from foundationdb_tpu.utils.rng import DeterministicRandom


def small_device_set(**kw):
    kw.setdefault("capacity", 1024)
    kw.setdefault("txns", 64)
    kw.setdefault("reads_per_txn", 4)
    kw.setdefault("writes_per_txn", 4)
    return DeviceConflictSet(**kw)


def both():
    return small_device_set(), OracleConflictSet()


def txn(snap, reads=(), writes=()):
    return TxnConflictInfo(read_snapshot=snap,
                           read_ranges=list(reads), write_ranges=list(writes))


def check(dev, oracle, txns, version):
    got = dev.detect(txns, version)
    want = oracle.detect(txns, version)
    assert got == want, f"device={got} oracle={want} @v{version}"
    return got


# ---------------------------------------------------------------------------
# targeted semantics
# ---------------------------------------------------------------------------

def test_blind_writes_always_commit():
    dev, oracle = both()
    s = check(dev, oracle, [txn(0, writes=[(b"a", b"b")])], 100)
    assert s == [COMMITTED]
    # same key again, stale snapshot, still a blind write -> commits
    s = check(dev, oracle, [txn(0, writes=[(b"a", b"b")])], 200)
    assert s == [COMMITTED]


def test_read_write_conflict_and_snapshot_isolation():
    dev, oracle = both()
    check(dev, oracle, [txn(0, writes=[(b"k", b"k\x00")])], 100)
    # snapshot before the write -> conflict
    s = check(dev, oracle, [txn(50, reads=[(b"k", b"k\x00")])], 200)
    assert s == [CONFLICT]
    # snapshot after the write -> fine
    s = check(dev, oracle, [txn(150, reads=[(b"k", b"k\x00")])], 300)
    assert s == [COMMITTED]


def test_adjacent_ranges_do_not_conflict():
    dev, oracle = both()
    check(dev, oracle, [txn(0, writes=[(b"a", b"b")])], 100)
    s = check(dev, oracle, [txn(50, reads=[(b"b", b"c")])], 200)  # [a,b) vs [b,c)
    assert s == [COMMITTED]
    s = check(dev, oracle, [txn(50, reads=[(b"a\xff\xff", b"b")])], 300)
    assert s == [CONFLICT]  # strictly inside [a,b)


def test_intra_batch_earlier_txn_wins_and_aborted_writes_invisible():
    dev, oracle = both()
    batch = [
        txn(0, writes=[(b"x", b"x\x00")]),                       # commits
        txn(0, reads=[(b"x", b"x\x00")], writes=[(b"y", b"y\x00")]),  # conflicts with t0
        txn(0, reads=[(b"y", b"y\x00")]),                        # t1 aborted -> commits
    ]
    s = check(dev, oracle, batch, 100)
    assert s == [COMMITTED, CONFLICT, COMMITTED]


def test_intra_batch_long_chain():
    dev, oracle = both()
    # t_i reads k_{i-1}, writes k_i: alternating commit/conflict down the chain
    batch = [txn(0, writes=[(b"k0", b"k0\x00")])]
    for i in range(1, 20):
        batch.append(txn(0, reads=[(b"k%d" % (i - 1), b"k%d\x00" % (i - 1))],
                         writes=[(b"k%d" % i, b"k%d\x00" % i)]))
    s = check(dev, oracle, batch, 100)
    assert s == [COMMITTED if i % 2 == 0 else CONFLICT for i in range(20)]


def test_own_writes_do_not_conflict_with_own_reads():
    dev, oracle = both()
    s = check(dev, oracle,
              [txn(0, reads=[(b"a", b"b")], writes=[(b"a", b"b")])], 100)
    assert s == [COMMITTED]


def test_too_old():
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 1000)
    dev, oracle = both()
    check(dev, oracle, [txn(0, writes=[(b"a", b"b")])], 5000)
    # window floor is now 4000; snapshot 100 with reads -> too old
    s = check(dev, oracle, [txn(100, reads=[(b"z", b"z\x00")])], 6000)
    assert s == [TOO_OLD]
    # blind write with ancient snapshot is fine
    s = check(dev, oracle, [txn(100, writes=[(b"z", b"z\x00")])], 6100)
    assert s == [COMMITTED]


def test_window_gc_clamps_but_keeps_recent():
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 1000)
    dev, oracle = both()
    check(dev, oracle, [txn(0, writes=[(b"a", b"b")])], 100)
    check(dev, oracle, [txn(50, writes=[(b"m", b"n")])], 1050)
    # write@100 is now below the floor (50); snapshot 60 >= floor... but
    # clamped values make any read of [a,b) with snapshot < floor too old and
    # with snapshot in [floor, 100) conflict-equivalent. Snapshot 60 reads m:
    s = check(dev, oracle, [txn(60, reads=[(b"m", b"n")])], 1100)
    assert s == [CONFLICT]  # write@1050 > 60


def test_empty_batch_and_empty_txn():
    dev, oracle = both()
    assert check(dev, oracle, [], 100) == []
    s = check(dev, oracle, [txn(0)], 200)
    assert s == [COMMITTED]


def test_range_write_vs_point_read():
    dev, oracle = both()
    check(dev, oracle, [txn(0, writes=[(b"a", b"q")])], 100)
    s = check(dev, oracle, [txn(10, reads=[(b"m", b"m\x00")])], 200)
    assert s == [CONFLICT]
    s = check(dev, oracle, [txn(10, reads=[(b"q", b"q\x00")])], 300)
    assert s == [COMMITTED]


def test_chunking_preserves_batch_order_semantics():
    dev = DeviceConflictSet(capacity=1024, txns=8, reads_per_txn=2, writes_per_txn=2)
    oracle = OracleConflictSet()
    # 20 txns in one logical batch -> 3 device chunks; decisions must match a
    # single oracle batch exactly.
    batch = [txn(0, writes=[(b"c0", b"c0\x00")])]
    for i in range(1, 20):
        batch.append(txn(0, reads=[(b"c%d" % (i - 1), b"c%d\x00" % (i - 1))],
                         writes=[(b"c%d" % i, b"c%d\x00" % i)]))
    got = dev.detect(batch, 100)
    want = oracle.detect(batch, 100)
    assert got == want


# ---------------------------------------------------------------------------
# randomized parity (the oracle test)
# ---------------------------------------------------------------------------

def _random_key(rng, space):
    return space[rng.randint(0, len(space) - 1)]


def _random_range(rng, space):
    a, b = _random_key(rng, space), _random_key(rng, space)
    if a == b:
        return (a, a + b"\x00")
    return (min(a, b), max(a, b))


@pytest.mark.parametrize("seed,chunked", [
    (1, False), (2, False), (3, False), (4, False),
    (1, True), (2, True), (3, True),
], ids=["1", "2", "3", "4", "chunked-1", "chunked-2", "chunked-3"])
def test_randomized_parity(seed, chunked):
    """Decisions identical to the oracle under heavy contention. The
    `chunked` cases run an 8-transaction shape, so that a logical batch of up
    to 20 is cut into several chunks, with transactions of zero ranges and
    one read in ten an empty real range (b == e), which must still count
    for too-old."""
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 500)
    rng = DeterministicRandom(seed)
    dev = (small_device_set(txns=8, reads_per_txn=3, writes_per_txn=3)
           if chunked else small_device_set())
    oracle = OracleConflictSet()
    # small key space -> heavy contention
    space = [bytes([97 + i]) + bytes([97 + j]) for i in range(6) for j in range(6)]
    version = 0
    for _batch in range(25):
        version += rng.randint(1, 300)
        txns = []
        for _ in range(rng.randint(1, 20 if chunked else 30)):
            snap = max(0, version - rng.randint(0, 800))
            reads = [_random_range(rng, space) for _ in range(rng.randint(0, 3))]
            writes = [_random_range(rng, space) for _ in range(rng.randint(0, 3))]
            if chunked and rng.randint(0, 9) == 0 and reads:
                reads[0] = (reads[0][0], reads[0][0])  # empty real range
            txns.append(txn(snap, reads, writes))
        check(dev, oracle, txns, version)


def test_oversized_txn_rejected():
    """A transaction with more ranges than the whole batch shape is refused
    by split_for_capacity before any chunk of its logical batch touches the
    state: the write that came in the same batch never lands, and the set
    answers the next batch as an oracle that never saw the refused one."""
    from foundationdb_tpu.utils.errors import FDBError
    dev = small_device_set(txns=4, reads_per_txn=2, writes_per_txn=2)
    oracle = OracleConflictSet()
    check(dev, oracle, [txn(0, writes=[(b"a", b"b")])], 100)
    ranges = [(bytes([97 + i]), bytes([98 + i])) for i in range(9)]
    for big in (txn(0, reads=ranges), txn(0, writes=ranges)):
        with pytest.raises(FDBError) as ei:
            dev.detect([txn(0, writes=[(b"m", b"n")]), big], 200)
        assert ei.value.name == "transaction_too_large"
    s = check(dev, oracle, [txn(150, reads=[(b"m", b"n")]),
                            txn(50, reads=[(b"a", b"b")])], 300)
    assert s == [COMMITTED, CONFLICT]


@pytest.mark.parametrize("seed", [11, 12])
def test_randomized_parity_long_keys_and_prefixes(seed):
    rng = DeterministicRandom(seed)
    dev = small_device_set()
    oracle = OracleConflictSet()
    # nested/prefix-structured keys up to 24 bytes (exact-width boundary)
    space = []
    for _ in range(40):
        depth = rng.randint(1, 4)
        space.append(b"/".join(rng.random_bytes(rng.randint(1, 5)) for _ in range(depth))[:24])
    version = 0
    for _batch in range(15):
        version += rng.randint(1, 200)
        txns = [txn(max(0, version - rng.randint(0, 400)),
                    [_random_range(rng, space) for _ in range(rng.randint(0, 4))],
                    [_random_range(rng, space) for _ in range(rng.randint(0, 4))])
                for _ in range(rng.randint(1, 20))]
        check(dev, oracle, txns, version)


def test_long_key_truncation_never_false_commits():
    """Keys sharing a 24-byte prefix collapse on device; the collapse must
    round range ENDS up, so committed writes on long keys stay in history
    (a collapsed-to-empty write range would be a false commit)."""
    dev = small_device_set()
    long_a = b"p" * 28 + b"AAAA"
    long_b = b"p" * 28 + b"BBBB"  # distinct keys, same 24B prefix
    dev.detect([txn(0, writes=[(long_a, long_a + b"\x00")])], 100)
    s = dev.detect([txn(50, reads=[(long_b, long_b + b"\x00")])], 200)
    assert s == [CONFLICT]  # false conflict (collapse) — but never a miss
    s = dev.detect([txn(150, reads=[(long_b, long_b + b"\x00")])], 300)
    assert s == [COMMITTED]  # fresh snapshot sees past the write


def test_inverted_write_range_does_not_cancel_other_writes():
    """An inverted range (end < begin) must be inert: in the coverage
    prefix-sum a reversed -1/+1 delta pair would cancel a real write's
    coverage and drop it from history (false commit)."""
    dev, oracle = both()
    batch = [txn(0, writes=[(b"c", b"a")]),  # inverted
             txn(0, writes=[(b"b", b"d")])]
    check(dev, oracle, batch, 100)
    s = check(dev, oracle, [txn(50, reads=[(b"b", b"b\x00")])], 200)
    assert s == [CONFLICT]  # txn2's write survived the inverted neighbor


def test_empty_and_inverted_ranges_are_inert_intra_batch():
    dev, oracle = both()
    batch = [
        txn(0, writes=[(b"a", b"z")]),
        txn(0, reads=[(b"m", b"m")]),          # empty read inside [a,z)
        txn(0, reads=[(b"q", b"c")]),          # inverted read
        txn(0, writes=[(b"zx", b"c")], reads=[]),  # inverted write
        txn(0, reads=[(b"zx", b"zx\x00")]),  # inside inverted write only: inert
    ]
    s = check(dev, oracle, batch, 100)
    assert s == [COMMITTED, COMMITTED, COMMITTED, COMMITTED, COMMITTED]


def test_chunked_batch_uses_pre_batch_window_floor():
    """The MVCC floor advances once per logical batch: a txn in a later
    chunk must not see the floor moved by an earlier chunk."""
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 1000)
    dev = DeviceConflictSet(capacity=1024, txns=2, reads_per_txn=2, writes_per_txn=2)
    oracle = OracleConflictSet()
    batch = [txn(4900, writes=[(b"a", b"b")]),
             txn(4900, writes=[(b"c", b"d")]),
             txn(100, reads=[(b"zz", b"zz\x00")])]  # 3rd txn -> 2nd chunk
    got = dev.detect(batch, 5000)
    want = oracle.detect(batch, 5000)
    assert got == want == [COMMITTED, COMMITTED, COMMITTED]
    # after the batch, the floor HAS advanced (4000): now it is too old
    got = dev.detect([txn(100, reads=[(b"zz", b"zz\x00")])], 5100)
    want = oracle.detect([txn(100, reads=[(b"zz", b"zz\x00")])], 5100)
    assert got == want == [TOO_OLD]


def test_overflow_leaves_set_with_untruncated_state():
    tiny = DeviceConflictSet(capacity=64, txns=32, reads_per_txn=1, writes_per_txn=1)
    import pytest as _pytest
    with _pytest.raises(Exception):
        v = 0
        for i in range(20):
            v += 10
            tiny.detect([txn(0, writes=[(b"%04d" % (i * 31 + j), b"%04da" % (i * 31 + j))])
                         for j in range(31)], v)
    # the state the set holds must still satisfy its own invariant: nb <= K
    assert int(tiny._state["nb"]) <= 64


def test_state_survives_many_batches_with_gc():
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 1000)
    rng = DeterministicRandom(99)
    dev = small_device_set(capacity=512)
    oracle = OracleConflictSet()
    space = [b"k%02d" % i for i in range(30)]
    version = 0
    for _ in range(60):
        version += rng.randint(50, 200)
        txns = [txn(max(0, version - rng.randint(0, 1500)),
                    [_random_range(rng, space)],
                    [_random_range(rng, space)])
                for _ in range(rng.randint(1, 10))]
        check(dev, oracle, txns, version)
    # GC must keep the boundary count bounded by the live key space
    assert int(dev._state["nb"]) <= 2 * len(space) + 2


def test_full_capacity_merge_above_all_boundaries():
    """Regression: with the state exactly full (nb == K), committing a write
    above every stored boundary must still record BOTH endpoints. A surplus
    bisection step used to return K+1 for past-the-end queries, shifting the
    union slots right and silently dropping the write's end boundary
    (persistent false conflicts, or a broken sorted invariant)."""
    cs = DeviceConflictSet(capacity=4, txns=4, reads_per_txn=1,
                           writes_per_txn=1)
    version = 1000
    # fill the state to exactly K=4 boundaries ("", k10, k20, k30): adjacent
    # writes at distinct versions share interior boundaries
    for lo, hi in ((10, 20), (20, 30)):
        txns = [TxnConflictInfo(
            read_snapshot=version - 1, read_ranges=[],
            write_ranges=[(lo.to_bytes(4, "big"), hi.to_bytes(4, "big"))])]
        version += 1
        assert cs.detect(txns, version) == [COMMITTED]
    # commit a write above all boundaries while window GC coalesces the old
    # segments (large version jump keeps within int32 offsets)
    version += 6_000_000
    w = TxnConflictInfo(read_snapshot=version - 1, read_ranges=[],
                        write_ranges=[(int(100).to_bytes(4, "big"),
                                       int(200).to_bytes(4, "big"))])
    assert cs.detect([w], version) == [COMMITTED]
    # a read strictly above the write's end must NOT see it
    r_above = TxnConflictInfo(read_snapshot=version - 1,
                              read_ranges=[(int(200).to_bytes(4, "big"),
                                            int(300).to_bytes(4, "big"))],
                              write_ranges=[])
    # a read overlapping the write must conflict
    r_hit = TxnConflictInfo(read_snapshot=version - 1,
                            read_ranges=[(int(150).to_bytes(4, "big"),
                                          int(160).to_bytes(4, "big"))],
                            write_ranges=[])
    assert cs.detect([r_above, r_hit], version + 1) == [COMMITTED, CONFLICT]


@pytest.mark.parametrize("seed", [21, 22])
def test_randomized_parity_narrow_engine(seed):
    """A key_bytes=16 engine (5 limbs — the width the reference's own
    microbench keys need, SkipList.cpp setK 16-byte keys) must make decisions
    identical to the oracle for keys within its exact width, including the
    >16-byte conservative-collapse contract."""
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 500)
    rng = DeterministicRandom(seed)
    dev = small_device_set(key_bytes=16)
    oracle = OracleConflictSet()
    space = [b"............" + bytes([97 + i, 97 + j])  # setK-shaped 14B keys
             for i in range(5) for j in range(5)]
    version = 0
    for _batch in range(20):
        version += rng.randint(1, 300)
        txns = [txn(max(0, version - rng.randint(0, 800)),
                    [_random_range(rng, space) for _ in range(rng.randint(0, 3))],
                    [_random_range(rng, space) for _ in range(rng.randint(0, 3))])
                for _ in range(rng.randint(1, 30))]
        check(dev, oracle, txns, version)


def test_narrow_engine_long_key_collapse_is_conservative():
    dev = small_device_set(key_bytes=16)
    long_a = b"p" * 20 + b"AAAA"
    long_b = b"p" * 20 + b"BBBB"  # distinct, same 16B prefix
    assert dev.detect([txn(0, writes=[(long_a, long_a + b"\x00")])], 100) \
        == [COMMITTED]
    # reading the OTHER long key with a stale snapshot: collapsed prefix
    # must conservatively conflict (never false-commit)
    s = dev.detect([txn(50, reads=[(long_b, long_b + b"\x00")])], 200)
    assert s == [CONFLICT]


def test_rebase_preserves_conflicts_and_rejects_saturated_snapshots():
    """Rebase correctness at the extremes: after a >2^29 version jump the
    engine still catches a conflict whose versions were shifted (offsets
    stay exact), and a snapshot so stale its offset would SATURATE at the
    NEG sentinel is REJECTED (TOO_OLD) — a saturated snapshot compares
    equal to 'no version' and would silently miss every conflict in the
    window (hardened by the round-5 verify drive)."""
    dev = small_device_set()
    assert dev.detect([txn(0, writes=[(b"a", b"a\x00")])], 10) == [COMMITTED]
    # one-rebase jump: offsets shift but stay representable -> exact verdict
    s = dev.detect([txn(5, reads=[(b"a", b"a\x00")],
                        writes=[(b"b", b"b\x00")])], (1 << 30) + 77)
    assert s == [CONFLICT], s
    # two-rebase jump: snapshot 5's offset falls below NEG -> conservative
    # rejection, never a false commit
    dev2 = small_device_set()
    assert dev2.detect([txn(0, writes=[(b"a", b"a\x00")])], 10) == [COMMITTED]
    s = dev2.detect([txn(5, reads=[(b"a", b"a\x00")],
                         writes=[(b"b", b"b\x00")])], 1 << 31)
    assert s == [TOO_OLD], s


# ---------------------------------------------------------------------------
# deep parity fuzz (round-7 verify drive): richer workload shapes than the
# uniform-span fuzz above — variable-length keys, getRange-style prefix
# ranges, point reads, snapshot-read-exempt txns (reads the client never
# submits as conflict ranges, i.e. blind writes), empty ranges — over
# >= 1000 seeded batches total, byte-identical to the oracle.
# ---------------------------------------------------------------------------

def _fuzz_key(rng):
    # variable-length keys over a 3-letter alphabet: dense prefix structure,
    # so prefix ranges nest and partially overlap constantly
    return bytes(rng.randint(97, 99) for _ in range(rng.randint(1, 5)))


def _fuzz_range(rng):
    a = _fuzz_key(rng)
    kind = rng.randint(0, 9)
    if kind < 4:  # point access: [k, k+\x00)
        return (a, a + b"\x00")
    if kind < 7:  # getRange(prefix): [k, k+\xff) — covers all children
        return (a, a + b"\xff")
    b = _fuzz_key(rng)  # arbitrary span between two keys
    if a == b:
        return (a, a + b"\x00")
    return (min(a, b), max(a, b))


def _fuzz_txn(rng, version):
    snap = max(0, version - rng.randint(0, 900))
    if rng.randint(0, 5) == 0:
        # snapshot-read txn: its reads are EXEMPT from conflict checking,
        # so the client submits only write ranges (blind write on device)
        return txn(snap, [], [_fuzz_range(rng) for _ in range(rng.randint(1, 3))])
    reads = [_fuzz_range(rng) for _ in range(rng.randint(0, 3))]
    writes = [_fuzz_range(rng) for _ in range(rng.randint(0, 3))]
    if rng.randint(0, 19) == 0 and reads:
        reads[0] = (reads[0][0], reads[0][0])  # empty range: inert but real
    return txn(snap, reads, writes)


@pytest.mark.parametrize("seed,batches", [
    (31, 260), (32, 260), (33, 260), (34, 260), (55, 20)],
    ids=["31", "32", "33", "34", "short-55"])
def test_deep_parity_fuzz(seed, batches):
    """>= 1000 batches across the seed set (4 x 260, and one short stream
    of 20), one long-lived engine pair per seed (state carries across
    batches: history-vs-intra interplay is the hard part of the kernel)."""
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 600)
    rng = DeterministicRandom(seed)
    dev = small_device_set()
    oracle = OracleConflictSet()
    version = 0
    for _batch in range(batches):
        version += rng.randint(1, 250)
        txns = [_fuzz_txn(rng, version) for _ in range(rng.randint(1, 24))]
        check(dev, oracle, txns, version)


def test_capped_rounds_fallback_parity():
    """With the sandwich capped at 1 round, deep dependency chains cannot
    converge on device; the host-exact fallback must still produce
    oracle-identical statuses (fresh sets per batch: unconverged merges are
    conservative, so only same-batch decisions are comparable)."""
    KNOBS.set("CONFLICT_INTRA_ROUNDS", 1)
    rng = DeterministicRandom(77)
    for trial in range(6):
        dev = small_device_set()
        oracle = OracleConflictSet()
        if trial == 0:
            # depth-20 chain: the worst case for a capped fixpoint
            batch = [txn(0, writes=[(b"k0", b"k0\x00")])]
            for i in range(1, 20):
                batch.append(txn(0, reads=[(b"k%d" % (i - 1), b"k%d\x00" % (i - 1))],
                                 writes=[(b"k%d" % i, b"k%d\x00" % i)]))
        else:
            batch = [_fuzz_txn(rng, 100) for _ in range(rng.randint(8, 30))]
        check(dev, oracle, batch, 100)


# ---------------------------------------------------------------------------
# the step's order: built from the batch's ranks in the sorted state, equal
# element for element to the stable sort of [state | rb | re | wb | we]
# ---------------------------------------------------------------------------

def _order_inputs(state, batch, shapes):
    """(bkeys, bk, bcls) as conflict_step hands them to _merged_order."""
    NR, NW = shapes.reads, shapes.writes
    bk = np.concatenate([np.asarray(batch[f]) for f in ("rb", "re", "wb", "we")],
                        axis=1)
    bcls = np.concatenate([np.full(NR, 2), np.zeros(NR), np.full(2 * NW, 2)]
                          ).astype(np.int32)
    return np.asarray(state["bkeys"]), bk, bcls


def _assert_constructed_order(state, batch, shapes):
    """_merged_order against _lex_sort_perm of the concatenation and its
    inverse, on one (state, encoded batch)."""
    import jax.numpy as jnp
    from foundationdb_tpu.ops import conflict as C
    K = shapes.capacity
    bkeys, bk, bcls = _order_inputs(state, batch, shapes)
    sidx, bpos, bperm, q, rank, p = map(np.asarray, C._merged_order(
        jnp.asarray(bkeys), jnp.asarray(bk), jnp.asarray(bcls)))
    cls = np.concatenate([np.ones(K, np.uint32), bcls.astype(np.uint32)])
    want = np.asarray(C._lex_sort_perm(jnp.asarray(np.concatenate(
        [np.concatenate([bkeys, bk], axis=1), cls[None]]))))
    inverse = np.empty_like(want)
    inverse[want] = np.arange(want.size, dtype=want.dtype)
    np.testing.assert_array_equal(sidx, want)
    np.testing.assert_array_equal(bpos, inverse[K:])
    # the sorted batch, M wide: the batch elements in the order's order
    np.testing.assert_array_equal(K + bperm, want[want >= K])
    np.testing.assert_array_equal(q, bk[:, bperm])
    np.testing.assert_array_equal(p, np.flatnonzero(want >= K))
    # state keys before each: the history check's bounds
    np.testing.assert_array_equal(rank, np.cumsum(want < K)[p])


def _assert_built_groups(state, batch, shapes):
    """The key groups the step builds M wide (_group_starts,
    _batch_key_ranks) against the ones read off the keys gathered into the
    merged order: the expressions the step used while it materialised
    `skeys = allk[:, sidx]`, kept here as the reference."""
    import jax.numpy as jnp
    from foundationdb_tpu.ops import conflict as C
    K = shapes.capacity
    bkeys, bk, bcls = _order_inputs(state, batch, shapes)
    sidx, bpos, bperm, q, rank, p = C._merged_order(
        jnp.asarray(bkeys), jnp.asarray(bk), jnp.asarray(bcls))
    q_new, ranks = C._batch_key_ranks(q, bperm)
    newgrp = C._group_starts(jnp.asarray(bkeys), state["nb"], sidx, q, q_new,
                             rank, p)

    sidx, bpos = np.asarray(sidx), np.asarray(bpos)
    skeys = np.concatenate([bkeys, bk], axis=1)[:, sidx]
    want_newgrp = np.concatenate(
        [[True], (skeys[:, 1:] != skeys[:, :-1]).any(axis=0)])
    is_batch = sidx >= K
    cum_b_excl = np.cumsum(is_batch) - is_batch
    grp_start_b = np.maximum.accumulate(np.where(want_newgrp, cum_b_excl, -1))
    first_b = is_batch & (cum_b_excl == grp_start_b)
    rank_grp = np.cumsum(first_b) - 1
    rank_carried = np.maximum.accumulate(np.where(first_b, rank_grp, -1))
    np.testing.assert_array_equal(np.asarray(newgrp), want_newgrp)
    np.testing.assert_array_equal(np.asarray(ranks), rank_carried[bpos])


def _order_engine(reads_div=1, **kw):
    """(shapes, encoder, jitted step, fresh state) at a small shape, its
    reads cut to a `reads_div`th (a one-sided bucket). The step waits for
    its result: the encoder hands out the same host buffers again, and a
    dispatch still reading them must not see the next batch."""
    import jax
    from foundationdb_tpu.ops import conflict as C
    shapes = C._resolve_shapes(**kw)
    shapes = dataclasses.replace(shapes, reads=shapes.reads // reads_div)
    compiled = C._compiled_step(shapes, 1000)

    def step(state, batch):
        return jax.block_until_ready(compiled(state, batch))

    return shapes, C.BatchEncoder(shapes), step, C.init_state(shapes)


def _writes_of(*ranges):
    return [txn(0, writes=[r]) for r in ranges]


def _order_case_live_boundary():
    """Every class of endpoint equal to a live boundary of the state."""
    shapes, enc, step, state = _order_engine(
        capacity=64, txns=8, reads_per_txn=2, writes_per_txn=2)
    state, _, _ = step(state, enc.encode_batch(
        _writes_of((b"b", b"d"), (b"f", b"h")), 10))
    batch = enc.encode_batch([
        txn(5, reads=[(b"b", b"d"), (b"d", b"f")], writes=[(b"d", b"f")]),
        txn(5, reads=[(b"a", b"b"), (b"h", b"i")], writes=[(b"h", b"z")]),
    ], 20)
    return state, batch, shapes


def _order_case_equal_endpoints():
    """rb, re, wb and we equal to each other within one batch (empty and
    adjacent ranges), on a state that holds the same key."""
    shapes, enc, step, state = _order_engine(
        capacity=64, txns=8, reads_per_txn=2, writes_per_txn=2)
    state, _, _ = step(state, enc.encode_batch(_writes_of((b"m", b"n")), 10))
    batch = enc.encode_batch([
        txn(5, reads=[(b"m", b"m"), (b"k", b"m")],
            writes=[(b"m", b"m"), (b"m", b"p")]),
        txn(5, reads=[(b"m", b"p")], writes=[(b"k", b"m")]),
    ], 20)
    return state, batch, shapes


def _order_case_padding_only():
    """An all-padding batch (0xFFFFFFFF limbs; class 2, and class 0 for re)
    against the fresh state's padding."""
    shapes, enc, _step, state = _order_engine(
        capacity=32, txns=4, reads_per_txn=2, writes_per_txn=2)
    return state, enc.encode_batch([], 10), shapes


def _order_case_nb_one():
    shapes, enc, _step, state = _order_engine(
        capacity=32, txns=4, reads_per_txn=2, writes_per_txn=2)
    assert int(state["nb"]) == 1
    batch = enc.encode_batch([
        txn(0, reads=[(b"", b"a")], writes=[(b"", b"\xff")]),
        txn(0, reads=[(b"a", b"b")], writes=[(b"a", b"b")]),
    ], 10)
    return state, batch, shapes


def _full_order_engine():
    """_order_engine at K = 4 with the state exactly full: "", k10, k20, k30
    (nb == K, no padding left; M = 16 > K)."""
    shapes, enc, step, state = _order_engine(
        capacity=4, txns=4, reads_per_txn=1, writes_per_txn=1)
    for v, (lo, hi) in ((1, (b"k10", b"k20")), (2, (b"k20", b"k30"))):
        state, _, _ = step(state, enc.encode_batch(_writes_of((lo, hi)), v))
    assert int(state["nb"]) == shapes.capacity
    return shapes, enc, state


def _order_case_nb_full_and_m_over_k():
    """The state exactly full (nb == K, no padding left) at a shape whose
    batch is wider than the state (M = 16 > K = 4)."""
    shapes, enc, state = _full_order_engine()
    batch = enc.encode_batch([
        txn(2, reads=[(b"k20", b"k30")], writes=[(b"k30", b"k40")]),
        txn(2, reads=[(b"", b"k10")], writes=[(b"k05", b"k10")]),
    ], 3)
    return state, batch, shapes


def _order_case_poisoned():
    shapes, enc, step, state = _order_engine(
        capacity=8, txns=8, reads_per_txn=1, writes_per_txn=1)
    state, _, info = step(state, enc.encode_batch(
        _writes_of(*[(b"%02d" % i, b"%02da" % i) for i in range(8)]), 10))
    assert bool(info["overflow"]) and bool(state["poisoned"])
    batch = enc.encode_batch([
        txn(5, reads=[(b"", b"01")], writes=[(b"", b"\xff")]),
        txn(5, reads=[(b"03", b"04")], writes=[(b"03", b"03a")]),
    ], 20)
    return state, batch, shapes


def _order_case_last_live_boundary():
    """All four classes of endpoint on the state's LAST live boundary, with
    every read slot used: no padding `re` stands between that boundary and
    the first padding slot, whose predecessor in the order is then a state
    row, a `re` equal to it before both."""
    shapes, enc, step, state = _order_engine(
        capacity=32, txns=2, reads_per_txn=2, writes_per_txn=2)
    state, _, _ = step(state, enc.encode_batch(_writes_of((b"b", b"t")), 10))
    batch = enc.encode_batch([
        txn(5, reads=[(b"t", b"u"), (b"b", b"t")], writes=[(b"t", b"u")]),
        txn(5, reads=[(b"a", b"t"), (b"t", b"t")], writes=[(b"a", b"t")]),
    ], 20)
    assert (batch["rtxn"] < shapes.txns).all()
    return state, batch, shapes


def _order_case_rank_zero_and_rank_k():
    """A `re` of b"" (rank 0: a batch row at position 0 of the order, before
    the state's first key) and, on a full state, keys above every boundary
    (rank K: batch rows behind the state's last slot)."""
    shapes, enc, state = _full_order_engine()
    batch = enc.encode_batch([
        txn(2, reads=[(b"", b"")], writes=[(b"", b"k10")]),
        txn(2, reads=[(b"k30", b"k50")], writes=[(b"k40", b"k50")]),
        txn(2, reads=[(b"k50", b"k60")], writes=[(b"k50", b"k60")]),
    ], 3)
    return state, batch, shapes


def _with_max_ends(batch, txn_no):
    """`batch` with one more read and one more write, both of transaction
    `txn_no` and both ending at MAX_LIMBS, the padding's key: no byte string
    encodes to it, so the rows are written in limbs."""
    from foundationdb_tpu.utils import keys as keylib
    batch = {k: np.array(v) for k, v in batch.items()}
    for b, e, t in (("rb", "re", "rtxn"), ("wb", "we", "wtxn")):
        slot = int(np.argmax(batch[t] >= batch["txn_valid"].size))
        batch[b][:, slot] = keylib.encode_key(b"x")
        batch[e][:, slot] = keylib.MAX_LIMBS
        batch[t][slot] = txn_no
    return batch


def _order_case_max_limbs():
    """Valid ranges that END at MAX_LIMBS, the key of the padding: equal to
    every padding slot of the state and to the batch's own padding rows."""
    shapes, enc, step, state = _order_engine(
        capacity=32, txns=4, reads_per_txn=2, writes_per_txn=2)
    state, _, _ = step(state, enc.encode_batch(_writes_of((b"b", b"d")), 10))
    batch = _with_max_ends(enc.encode_batch(
        [txn(15, reads=[(b"c", b"x")], writes=[(b"d", b"x")])], 20), 0)
    return state, batch, shapes


def _order_case_live_max_limbs():
    """A state whose last LIVE key is MAX_LIMBS (the step before committed a
    write that ends there): the first padding slot then opens no group."""
    state, batch, shapes = _order_case_max_limbs()
    from foundationdb_tpu.ops import conflict as C
    from foundationdb_tpu.utils import keys as keylib
    state, _, _ = C._compiled_step(shapes, 1000)(state, batch)
    nb = int(state["nb"])
    np.testing.assert_array_equal(
        np.asarray(state["bkeys"])[:, nb - 1], keylib.MAX_LIMBS)
    batch = _with_max_ends(C.BatchEncoder(shapes).encode_batch([
        txn(25, reads=[(b"d", b"y")], writes=[(b"y", b"z")]),
        txn(25, reads=[(b"a", b"b")]),
    ], 30), 1)
    return state, batch, shapes


_ORDER_SPACE = [b"k%02d" % i for i in range(40)] + [b"", b"k07\x00", b"\xff"]
ORDER_CASES = [
    _order_case_live_boundary, _order_case_equal_endpoints,
    _order_case_padding_only, _order_case_nb_one,
    _order_case_nb_full_and_m_over_k, _order_case_poisoned,
    _order_case_last_live_boundary, _order_case_rank_zero_and_rank_k,
    _order_case_max_limbs, _order_case_live_max_limbs,
]


def _case_id(f):
    return f.__name__.removeprefix("_order_case_")


@pytest.mark.parametrize("case", ORDER_CASES, ids=_case_id)
def test_constructed_order_tie_cases(case):
    _assert_constructed_order(*case())


@pytest.mark.parametrize("case", ORDER_CASES, ids=_case_id)
def test_built_groups_tie_cases(case):
    _assert_built_groups(*case())


@pytest.mark.parametrize("seed,kw", [
    (41, {}), (42, {}), (43, {}),
    # a one-sided bucket (reads = full/16, writes = full), as bucket_shapes
    # hands a write-only workload's batches
    (44, {"reads_per_txn": 8, "bucket": True}),
    (45, {"reads_per_txn": 8, "bucket": True}),
    (46, {"key_bytes": 8}), (47, {"key_bytes": 8}),
    (48, {"capacity": 16, "txns": 8}),  # M = 64 > K; overflows on the way
], ids=["dynamic-41", "dynamic-42", "dynamic-43", "bucket-44", "bucket-45",
        "key_bytes8-46", "key_bytes8-47", "m_over_k-48"])
def test_constructed_order_random(seed, kw):
    """Random states (whatever the step itself left behind: merged, window
    collected, poisoned) and random batches: the constructed order is the
    stable sort's and the built key groups are the gathered keys', before
    every step."""
    kw = {"capacity": 256, "txns": 16, "reads_per_txn": 2,
          "writes_per_txn": 2, **kw}
    reads_div = 16 if kw.pop("bucket", False) else 1
    shapes, enc, step, state = _order_engine(reads_div=reads_div, **kw)
    rng = DeterministicRandom(seed)
    space = _ORDER_SPACE
    version = 0
    for _ in range(12):
        version += rng.randint(50, 300)
        txns = [txn(max(0, version - rng.randint(0, 1500)),
                    [_random_range(rng, space)
                     for _ in range(rng.randint(0, 2))],
                    [_random_range(rng, space)
                     for _ in range(rng.randint(0, 2))])
                for _ in range(rng.randint(0, kw["txns"]))]
        room = shapes.reads  # the bucket holds few reads; the rest go
        for t in txns:
            t.read_ranges = t.read_ranges[:room]
            room -= len(t.read_ranges)
        batch = enc.encode_batch(txns, version)
        _assert_constructed_order(state, batch, shapes)
        _assert_built_groups(state, batch, shapes)
        state, _, _ = step(state, batch)


# ---------------------------------------------------------------------------
# the precondition the built key groups lean on: whatever writes a state
# leaves its live keys DISTINCT and in order, and all-ones padding behind
# ---------------------------------------------------------------------------

def assert_state_keys_distinct(bkeys, nb):
    """`bkeys` ((L, K)): the first `nb` keys strictly increasing, every later
    slot MAX_LIMBS."""
    from foundationdb_tpu.utils import keys as keylib
    bkeys, nb = np.asarray(bkeys), int(nb)
    assert 1 <= nb <= bkeys.shape[1]
    live = [tuple(int(x) for x in col) for col in bkeys[:, :nb].T]
    assert all(a < b for a, b in zip(live, live[1:])), "live keys not distinct"
    assert (bkeys[:, nb:] == keylib.MAX_LIMBS[:len(bkeys), None]).all()


def _after_random_steps(dev, rng):
    space, version = _ORDER_SPACE, 0
    for _ in range(12):
        version += rng.randint(50, 300)
        dev.detect([txn(max(0, version - rng.randint(0, 1500)),
                        [_random_range(rng, space)], [_random_range(rng, space)])
                    for _ in range(rng.randint(1, 16))], version)
        yield version


def _after_overflow(dev, rng):
    try:
        for v in range(1, 8):
            dev.detect([txn(0, writes=[(b"%04d" % (v * 40 + j),
                                        b"%04da" % (v * 40 + j))])
                        for j in range(16)], v * 10)
            yield v * 10
    except Exception as e:  # noqa: BLE001 — the overflow the case is after
        assert "capacity exceeded" in str(e)
    assert bool(dev._state["poisoned"])
    yield 100


def _after_rebase(dev, rng):
    *_, version = _after_random_steps(dev, rng)
    base = dev.base_version
    dev.detect([txn(version, writes=[(b"k01", b"k02")])], version + (1 << 30))
    assert dev.base_version > base
    yield version + (1 << 30)


def _after_clear(dev, rng):
    list(_after_random_steps(dev, rng))
    dev.clear(oldest_version=5)
    yield 5
    dev.detect([txn(5, writes=[(b"a", b"b")])], 50)
    yield 50


@pytest.mark.parametrize("events,capacity", [
    (_after_random_steps, 256), (_after_overflow, 64),
    (_after_rebase, 256), (_after_clear, 256),
], ids=["random_steps", "overflow", "rebase", "clear"])
def test_state_keys_stay_distinct_and_padded(events, capacity):
    """After every step of each history (merges with window collection, the
    poisoning overflow, a rebase of the versions, a clear) the state is what
    conflict_step's docstring asks of its input."""
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 1000)
    dev = small_device_set(capacity=capacity, txns=16)
    assert_state_keys_distinct(dev._state["bkeys"], dev._state["nb"])
    for _version in events(dev, DeterministicRandom(61)):
        assert_state_keys_distinct(dev._state["bkeys"], dev._state["nb"])


# ---------------------------------------------------------------------------
# the serving jaxpr contains NO unbounded while_loop
# ---------------------------------------------------------------------------

def test_serving_jaxpr_has_no_while_loop():
    """The tentpole's structural guarantee: the serving detect path lowers to
    bounded control flow only (scan/cond) — an unbounded `while` primitive
    would reintroduce the data-dependent fixpoint the overhaul removed."""
    import jax
    from foundationdb_tpu.ops import conflict as C
    dev = small_device_set()
    state = C.init_state(dev.shapes)
    batch = dev.encoder.encode_batch(
        [txn(0, reads=[(b"a", b"b")], writes=[(b"c", b"d")])], 100)
    life = KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS

    serving = str(jax.make_jaxpr(
        lambda s, b: C.conflict_step(s, b, shapes=dev.shapes,
                                     max_write_life=life))(state, batch))
    assert "while[" not in serving, "unbounded fixpoint back in serving path"
    assert "scan[" in serving  # the bounded sandwich is there


# ---------------------------------------------------------------------------
# a state whose fill follows the write rate: inserts and evictions every step
# ---------------------------------------------------------------------------

def test_fill_and_evictions_follow_the_oracle_past_the_window():
    """Key space 20x a window's writes, versions advancing past the window
    (chip_smoke.py's stream, which the chip runs at the served capacity):
    every step inserts boundaries and, once the window is full, evicts about
    as many; the state's fill is set by the rate, not by the key count. The
    step's own account (info -> DetectHandle.steps) is the oracle's after
    every step, at a capacity that is nobody's default."""
    from chip_smoke import _oracle_fill_step, _window_stream
    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", 1000)
    dev = small_device_set(capacity=768)  # holds ~ 2 x 240 writes a window
    oracle = OracleConflictSet()
    held = oracle.live_boundaries()
    fills, dropped, verdicts = [], [], []
    for txns, version in _window_stream(3201, n_batches=48, n_keys=4800,
                                        step=100, txns=8, sets=3):
        handle = dev.detect_async(txns, version)
        got = handle.result()
        want, held, evicted = _oracle_fill_step(oracle, txns, version, held)
        assert got == want, f"device={got} oracle={want} @v{version}"
        assert handle.steps == [(len(held), evicted)], version
        assert len(held) == int(dev._state["nb"])
        fills.append(len(held))
        dropped.append(evicted)
        verdicts += got
    assert {COMMITTED, CONFLICT, TOO_OLD} <= set(verdicts)
    # the window filled in ten steps; from then on every step drops rows and
    # the fill stands where the rate puts it, far under the 9,600 of the keys
    assert all(d > 0 for d in dropped[12:])
    assert 300 < min(fills[12:]) and max(fills) < 768
    assert sum(dropped) > 3 * max(fills)
