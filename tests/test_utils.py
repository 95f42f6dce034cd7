"""Unit tests for the utility layer (keys, errors, knobs, rng)."""

import numpy as np
import pytest

from foundationdb_tpu.utils import keys as K
from foundationdb_tpu.utils.errors import FDBError
from foundationdb_tpu.utils.knobs import KNOBS
from foundationdb_tpu.utils.rng import DeterministicRandom


def test_key_encoding_roundtrip():
    for k in [b"", b"a", b"abc", b"\x00", b"\xff" * 10, b"x" * 24]:
        assert K.decode_key(K.encode_key(k)) == k


def test_key_encoding_order_matches_bytes_order():
    rng = DeterministicRandom(1)
    ks = [rng.random_bytes(rng.randint(0, 24)) for _ in range(300)]
    ks += [b"abc", b"abc\x00", b"abd", b"ab", b"", b"\xff" * 24]
    enc = [K.encode_key(k) for k in ks]
    for i in range(len(ks)):
        for j in range(i + 1, len(ks)):
            want = (ks[i] > ks[j]) - (ks[i] < ks[j])
            got = K.compare_encoded(enc[i], enc[j])
            assert got == want, (ks[i], ks[j])


def test_key_truncation_is_prefix_collapse():
    long1 = b"p" * 24 + b"a"
    long2 = b"p" * 24 + b"b"
    assert K.compare_encoded(K.encode_key(long1), K.encode_key(long2)) == 0
    assert K.compare_encoded(K.encode_key(b"p" * 24), K.encode_key(long1)) == 0


def test_max_sentinel_greater_than_all():
    for k in [b"", b"\xff" * 24, b"\xff" * 100]:
        assert K.compare_encoded(K.encode_key(k), K.MAX_LIMBS) == -1


def test_encode_keys_batch():
    ks = [b"a", b"bb", b"ccc"]
    arr = K.encode_keys(ks)
    assert arr.shape == (K.NUM_LIMBS, 3)
    for i, k in enumerate(ks):
        assert K.decode_key(arr[:, i]) == k


def test_strinc_and_key_after():
    assert K.strinc(b"a") == b"b"
    assert K.strinc(b"a\xff\xff") == b"b"
    assert K.key_after(b"a") == b"a\x00"
    with pytest.raises(ValueError):
        K.strinc(b"\xff")


def test_errors():
    e = FDBError("not_committed")
    assert e.code == 1020 and e.is_retryable
    e2 = FDBError("io_error")
    assert not e2.is_retryable
    with pytest.raises(ValueError):
        FDBError("no_such_error")


def test_knobs_buggify_deterministic():
    r1, r2 = DeterministicRandom(7), DeterministicRandom(7)
    KNOBS.buggify(r1)
    snap1 = dict(KNOBS._values)
    KNOBS.reset()
    KNOBS.buggify(r2)
    assert dict(KNOBS._values) == snap1


def test_buggified_draw_ignores_the_rest_of_the_registry():
    """What a seed does to one knob depends on the seed and the knob's name,
    not on which other knobs are registered: deleting or adding a knob must
    not re-roll every simulation seed."""
    from foundationdb_tpu.utils.knobs import Knobs
    names = [f"KNOB_{c}" for c in "BCDEFGHIJKLMNOPQ"]

    def draws(extra: dict) -> list[dict]:
        bank = Knobs()
        for n in names:
            bank.init(n, 0, (1, 2, 3))
        for n, extremes in extra.items():
            bank.init(n, 0, extremes)
        return [bank.draw_buggified(DeterministicRandom(seed), 0.5)
                for seed in range(40)]

    alone = draws({})
    assert any(alone) and len({tuple(sorted(d.items())) for d in alone}) > 20
    # a knob that sorts before all of them, one in their middle, and one
    # that declares no extremes
    crowded = draws({"KNOB_A": (7, 8), "KNOB_HH": (9,), "KNOB_0": ()})
    for was, now in zip(alone, crowded):
        assert {k: v for k, v in now.items() if k in names} == was


def test_rng_determinism():
    a, b = DeterministicRandom(42), DeterministicRandom(42)
    assert [a.randint(0, 100) for _ in range(10)] == [b.randint(0, 100) for _ in range(10)]
    assert a.fork().random() == b.fork().random()


class TestIndexedSet:
    """flow/IndexedSet.h parity: the C skiplist and the Python fallback make
    identical decisions (insert/discard/rank/nth/ranges/sums), and the
    augmented sums answer range metrics in O(log n)."""

    def _pair(self):
        from foundationdb_tpu.utils.indexedset import (
            PyIndexedSet, make_indexed_set)
        return make_indexed_set(), PyIndexedSet()

    def test_fuzz_parity_with_python_fallback(self):
        import random
        s, p = self._pair()
        rng = random.Random(99)
        for _ in range(3000):
            op = rng.random()
            k = b"k%05d" % rng.randrange(900)
            if op < 0.55:
                m = rng.randrange(1, 50)
                s.insert(k, m)
                p.insert(k, m)
            elif op < 0.75:
                assert s.discard(k) == p.discard(k)
            else:
                lo = b"k%05d" % rng.randrange(900)
                hi = b"k%05d" % rng.randrange(900)
                if lo > hi:
                    lo, hi = hi, lo
                assert s.rank(lo) == p.rank(lo)
                assert tuple(s.sum_range(lo, hi)) == tuple(p.sum_range(lo, hi))
                assert s.range_keys(lo, hi, 7, False) == \
                    p.range_keys(lo, hi, 7, False)
                assert s.range_keys(lo, hi, 7, True) == \
                    p.range_keys(lo, hi, 7, True)
        assert len(s) == len(p)
        for i in (0, len(p) // 3, len(p) - 1):
            if 0 <= i < len(p):
                assert s.nth(i) == p.nth(i)

    def test_metric_replace_updates_sums(self):
        s, _ = self._pair()
        s.insert(b"a", 10)
        s.insert(b"b", 20)
        s.insert(b"c", 30)
        assert tuple(s.sum_range(b"a", b"d")) == (3, 60)
        s.insert(b"b", 5)  # re-metric
        assert tuple(s.sum_range(b"a", b"d")) == (3, 45)
        assert tuple(s.sum_range(b"b", b"c")) == (1, 5)

    def test_lazy_iteration_matches_range(self):
        from foundationdb_tpu.utils.indexedset import iter_range
        s, _ = self._pair()
        for i in range(500):
            s.insert(b"%05d" % i, 1)
        assert list(iter_range(s, b"00100", b"00400", chunk=13)) == \
            [b"%05d" % i for i in range(100, 400)]
        assert list(iter_range(s, b"00100", b"00400", reverse=True,
                               chunk=7)) == \
            [b"%05d" % i for i in range(399, 99, -1)]
