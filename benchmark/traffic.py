"""The one traffic generator and the one data maker. Both read data files:
`configs/<config>.json` gives the data (count, key format, value shape),
`traffic/<mix>.json` gives the mix (operations per transaction, shares of
each operation, request distribution, concurrent clients).

Everything is a pure function of `--seed`: the loaded data, and for every
(worker, actor) one stream of transaction plans. A plan is drawn whole before
its first attempt, so a retry repeats it and the reference (reference.py) can
replay each actor's stream from the seed and the worker's log alone.

Imports nothing of the program.
"""

from __future__ import annotations

import random

import numpy as np

READ, SET, RMW = 0, 1, 2
OPS = {"read": READ, "set": SET, "rmw": RMW}
# a read of a key that storage does not hold goes into the checksum as this
MISSING = b"\xff<missing>"

# YCSB core, generator/ZipfianGenerator.java + ScrambledZipfianGenerator.java:
# the scrambled generator draws from a zipfian over ITEM_COUNT items with a
# precomputed zeta, then spreads the ranks with FNV-64 modulo the record count
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302  # zeta(ITEM_COUNT, 0.99), YCSB's constant
_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 1099511628211
_M64 = (1 << 64) - 1


def fnvhash64(val: int) -> int:
    """YCSB Utils.fnvhash64: FNV-1 over the 8 octets, low first, in Java's
    signed 64-bit arithmetic, absolute value at the end."""
    h = _FNV_OFFSET
    for _ in range(8):
        h = ((h ^ (val & 0xFF)) * _FNV_PRIME) & _M64
        val >>= 8
    if h >> 63:  # negative as a Java long
        h = (-h) & _M64
        if h >> 63:  # Long.MIN_VALUE: Math.abs leaves it negative
            return h - (1 << 64)
    return h


def fnvhash64_array(vals: np.ndarray) -> np.ndarray:
    """fnvhash64 over an array of non-negative ints (same arithmetic)."""
    v = vals.astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * np.uint64(_FNV_PRIME)
            v = v >> np.uint64(8)
        neg = (h >> np.uint64(63)).astype(bool)
        h = np.where(neg, (~h) + np.uint64(1), h)
    return h


class Zipfian:
    """YCSB's ZipfianGenerator.nextValue (Gray et al., "Quickly generating
    billion-record synthetic databases"), ranks from 0."""

    def __init__(self, items: int, theta: float, zetan: float):
        self.items, self.theta, self.zetan = items, theta, zetan
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = 1.0 + 0.5 ** theta
        self.eta = ((1.0 - (2.0 / items) ** (1.0 - theta))
                    / (1.0 - zeta2 / zetan))
        self.second = 1.0 + 0.5 ** theta

    def rank(self, u: float) -> int:
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < self.second:
            return 1
        return int(self.items * (self.eta * u - self.eta + 1.0) ** self.alpha)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta))


# ---------------------------------------------------------------- the data

class Data:
    """The records a configuration loads, made from the seed: keys by the
    configuration's format, values by its shape."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.count = int(spec["records"])
        self.value = spec["value"]
        fmt = spec["key"]
        if fmt["kind"] == "decimal":  # b"%016d" % i
            width = int(fmt["bytes"])
            self.keys = [b"%0*d" % (width, i) for i in range(self.count)]
        elif fmt["kind"] == "ycsb_hashed":  # "user" + fnvhash64(i)
            prefix = fmt["prefix"].encode()
            hashed = fnvhash64_array(np.arange(self.count))
            self.keys = [prefix + b"%d" % int(h) for h in hashed]
        else:
            raise ValueError(f"unknown key kind {fmt['kind']!r}")
        if len(set(self.keys)) != self.count:
            raise ValueError("the key format maps two records onto one key")
        self.index = {k: i for i, k in enumerate(self.keys)}
        self.seed = seed

    def initial_values(self) -> list[bytes]:
        rng = random.Random(_mix(self.seed, 0x10AD))
        v = self.value
        if v["kind"] == "bytes":
            lo, hi = int(v["min"]), int(v["max"])
            lens = [rng.randint(lo, hi) for _ in range(self.count)]
            blob = rng.randbytes(sum(lens))
            out, at = [], 0
            for n in lens:
                out.append(blob[at:at + n])
                at += n
            return out
        if v["kind"] == "record":
            f, n = int(v["fields"]), int(v["field_bytes"])
            blob = rng.randbytes(self.count * f * n)
            return [encode_record([blob[(i * f + j) * n:(i * f + j + 1) * n]
                                   for j in range(f)])
                    for i in range(self.count)]
        raise ValueError(f"unknown value kind {v['kind']!r}")

    def cut_keys(self, shards: int) -> list[bytes]:
        """Storage shard cuts that give each shard the same number of keys."""
        ordered = sorted(self.keys)
        return [ordered[self.count * s // shards] for s in range(1, shards)]


POOL_BYTES = 1 << 20
FRAME = 3  # bytes of framing a field: its index, and its length in two bytes


def encode_record(fields: list[bytes]) -> bytes:
    """The benchmark's record codec: each field as index, length, bytes."""
    return b"".join(bytes([j]) + len(f).to_bytes(2, "big") + f
                    for j, f in enumerate(fields))


def replace_field(record: bytes, j: int, fresh: bytes) -> bytes:
    """The record with field j's bytes replaced (all fields are as long as
    `fresh`, so the field's place follows from j)."""
    at = j * (FRAME + len(fresh)) + FRAME
    return record[:at] + fresh + record[at + len(fresh):]


def _mix(*parts: int) -> int:
    """One 64-bit seed out of several numbers (splitmix64 steps)."""
    x = 0x9E3779B97F4A7C15
    for p in parts:
        x = (x ^ (p & _M64)) & _M64
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
        x ^= x >> 31
    return x


# ------------------------------------------------------------- the traffic

class Traffic:
    """Plans of transactions for one mix over one data set."""

    def __init__(self, mix: dict, data: Data):
        self.mix, self.data = mix, data
        self.ops_per_txn = int(mix["ops_per_txn"])
        shares = mix["operations"]
        total = float(sum(shares.values()))
        self.cdf, acc = [], 0.0
        for name, share in shares.items():
            acc += share / total
            self.cdf.append((acc, OPS[name]))
        self.cdf[-1] = (1.0, self.cdf[-1][1])  # rounding cannot fall past it
        dist = mix["request_distribution"]
        self.n = data.count
        if dist["kind"] == "uniform":
            self.zipf = None
        elif dist["kind"] == "scrambled_zipfian":
            self.zipf = Zipfian(YCSB_ITEM_COUNT, float(dist["constant"]),
                                YCSB_ZETAN)
        else:
            raise ValueError(f"unknown distribution {dist['kind']!r}")
        v = data.value
        self.record = v["kind"] == "record"
        if self.record:
            self.fields = int(v["fields"])
            self.field_bytes = int(v["field_bytes"])
            self.widest = self.fields * self.field_bytes
        else:
            self.vmin, self.vmax = int(v["min"]), int(v["max"])
            self.widest = self.vmax

    def actor_rng(self, seed: int, worker: int, actor: int) -> random.Random:
        return random.Random(_mix(seed, 0xAC7, worker, actor))

    def _key(self, rng: random.Random) -> int:
        if self.zipf is None:
            return int(rng.random() * self.n)
        return fnvhash64(self.zipf.rank(rng.random())) % self.n

    def make_pool(self, seed: int) -> bytes:
        """The bytes every fresh value is a slice of (drawing each value's
        bytes anew would cost the load generator more than the client API)."""
        return random.Random(_mix(seed, 0x9001)).randbytes(
            POOL_BYTES + self.widest)

    def plan(self, rng: random.Random) -> list[tuple]:
        """The next transaction of an actor, as (op, record number, a, b):
        `a` is where the fresh bytes start in the pool; `b` is their length
        for a plain value, and for a record the field an `rmw` replaces."""
        out = []
        for _ in range(self.ops_per_txn):
            u = rng.random()
            op = next(o for edge, o in self.cdf if u <= edge)
            k = self._key(rng)
            if op == READ:
                out.append((READ, k, 0, 0))
            elif self.record:
                out.append((op, k, int(rng.random() * POOL_BYTES),
                            int(rng.random() * self.fields)))
            else:
                out.append((op, k, int(rng.random() * POOL_BYTES),
                            self.vmin + int(rng.random()
                                            * (self.vmax - self.vmin + 1))))
        return out

    def fresh(self, pool: bytes, op: int, a: int, b: int) -> bytes:
        """The fresh bytes of a write: a plain value, one field of a record
        (`rmw`, YCSB's default of one field an update) or a whole record
        (`set`, YCSB's writeallfields)."""
        if not self.record:
            return pool[a:a + b]
        if op == RMW:
            return pool[a:a + self.field_bytes]
        n = self.field_bytes
        return encode_record([pool[a + j * n:a + (j + 1) * n]
                              for j in range(self.fields)])

    def modify(self, old: bytes | None, fresh: bytes, b: int) -> bytes:
        """What an `rmw` writes back, given what it read."""
        if not self.record:
            return fresh
        if old is None:  # not on loaded data; keeps the record's shape
            old = encode_record([bytes(self.field_bytes)] * self.fields)
        return replace_field(old, b, fresh)
