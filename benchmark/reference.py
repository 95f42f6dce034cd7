"""The plain reference: what a strictly serializable, durable key-value store
answers to the transactions a run acknowledged. Imports nothing of the
program, and takes from a run only what clients saw: each transaction's read
version, commit version and the checksum of what it read, and the rows read
back from storage once the window had closed.

`check_history` replays every actor's plans from the seed, applies the
acknowledged writes in commit-version order to a dict, and counts

- `readback_mismatches`: records whose value read back from storage is not
  the last acknowledged write (durability: an acknowledged commit is readable),
- `read_mismatches`: acknowledged transactions whose reads are not the values
  as of their read version (every read of the run, not a sample),
- `conflict_violations`: acknowledged transactions that read a key which
  another acknowledged transaction wrote between their read and commit
  versions (the resolver's verdicts: of two that conflict, one is refused),
Every one of these has the limit 0. A transaction that ended in an error
other than `not_committed` (the program may answer `commit_unknown_result`
when a server stood still) is a failed operation, not a wrong answer: run.py
counts it under `failed` and as missing every latency, and here the records it
may have written are left out of the comparison, since nobody knows whether
it committed. No control and no planted fault raises that count, so it has no
upper reading and is not among the numbers compared. Two blind writes of one
key at one commit version (one commit batch) by two transactions may be
serialized either way, and a client cannot tell which: then either value is
accepted, and the count of such ties is reported beside the numbers.

A plan is replayed as the client API runs it (read-your-writes): a read of a
record the same plan has written before is answered from that write, with no
read version, no read of the database and no read conflict, so it goes into
the checksum with the plan's own value and can be no violation; of two writes
of one record in one plan the later one stands.

`RefCluster` is the same semantics as a system, for control.py: it stands in
the program's place, with one stated guarantee broken.
"""

from __future__ import annotations

import bisect
import itertools
import math
import zlib

from actor import ACKNOWLEDGED
from traffic import MISSING, READ, RMW, SET

LIMITS = {"readback_mismatches": 0, "read_mismatches": 0,
          "conflict_violations": 0}


def _alts(v):
    """A value the model is sure of is bytes; after a tie it is a tuple."""
    return v if isinstance(v, tuple) else (v,)


MAX_ALTERNATIVES = 64


def _norm(alts: set):
    """One value, or the tuple of those a tie left possible. In a sound run
    only blind writes of one commit batch tie, so there are as many as that
    batch had writers of the key. A system that lets two read-modify-writes
    of one record through in one batch (which is counted as a violation
    where it happens) would double them batch after batch: they are cut
    there, since what such a run reads is no longer judged by them."""
    if len(alts) == 1:
        return next(iter(alts))
    return tuple(sorted(alts, key=lambda v: (v is None, v))[:MAX_ALTERNATIVES])


def _written(traffic, pool: bytes, op: int, a: int, b: int, read) -> tuple:
    """The values a `set` or an `rmw` may write, the latter given those its
    read (of the database, or of the plan's own earlier write) may have
    returned."""
    fresh = traffic.fresh(pool, op, a, b)
    if op == SET:
        return (fresh,)
    return tuple({traffic.modify(old, fresh, b) for old in read})


def check_history(traffic, seed: int, pool: bytes, initial: list[bytes],
                  logs: dict, readback: dict | None) -> tuple[dict, dict]:
    """(numbers, notes). `logs` maps a worker's number to its LOG_DTYPE rows;
    `readback` maps record number to the value storage returned (None: the
    final state was not read)."""
    latest = list(initial)
    last_version = [0] * len(initial)
    writing, reading, tainted = [], [], set()
    failed = n_txns = 0
    for worker, rows in sorted(logs.items()):
        order = sorted(range(len(rows)),
                       key=lambda i: (rows["actor"][i], rows["seq"][i]))
        rng, at = None, None
        for i in order:
            row = rows[i]
            actor = int(row["actor"])
            if actor != at:
                rng, at, want = traffic.actor_rng(seed, worker, actor), actor, 0
            if int(row["seq"]) != want:
                raise ValueError(f"worker {worker} actor {actor}: the log "
                                 f"lacks transaction {want}")
            want += 1
            plan = traffic.plan(rng)
            n_txns += 1
            writes = any(op != READ for op, _k, _a, _b in plan)
            if row["status"] != ACKNOWLEDGED:
                failed += 1
                tainted.update(k for op, k, _a, _b in plan if op != READ)
                continue
            if writes:
                writing.append((int(row["cv"]), worker, actor, int(row["rv"]),
                                plan))
            if any(op != SET for op, _k, _a, _b in plan):
                reading.append((int(row["rv"]), int(row["crc"]), plan))

    need_history = bool(reading)
    hist_v = {}  # record -> versions written in the run, ascending
    hist_x = {}  # record -> the value from each of them on
    violations = ties = 0
    writing.sort(key=lambda t: t[0])
    for cv, group in itertools.groupby(writing, key=lambda t: t[0]):
        group = list(group)
        new = {}  # record -> set of values it may hold after this version
        rmw_writers = {}
        for _cv, _w, _a, rv, plan in group:
            own = {}  # record -> what this plan has written to it so far
            for op, k, a, b in plan:
                mine = own.get(k)
                if op != SET and mine is None and last_version[k] > rv:
                    violations += 1  # read k at rv; k was written before cv
                if op == READ:
                    continue
                if op == RMW and mine is None:  # it read the database's value
                    rmw_writers[k] = rmw_writers.get(k, 0) + 1
                    mine = _alts(latest[k])
                own[k] = _written(traffic, pool, op, a, b, mine)
            for k, vals in own.items():
                if k in new:
                    ties += 1
                    new[k].update(vals)
                else:
                    new[k] = set(vals)
        # two transactions of one batch that both read and wrote k: the
        # second one's read was stale whichever came first
        violations += sum(n - 1 for n in rmw_writers.values() if n > 1)
        for k, vals in new.items():
            latest[k] = _norm(vals)
            last_version[k] = cv
            if need_history:
                hist_v.setdefault(k, []).append(cv)
                hist_x.setdefault(k, []).append(latest[k])

    # every read of every acknowledged transaction: a record the plan wrote
    # before, from the plan; any other, from the history as of the read version
    read_mismatches = reads_compared = ambiguous = 0
    for rv, crc, plan in reading:
        seen = []  # for each read in order, the values it may have returned
        own = {}
        unsure = False
        for op, k, a, b in plan:
            mine = own.get(k)
            if op != SET:
                if mine is None:
                    if k in tainted:
                        break
                    vs = hist_v.get(k)
                    j = bisect.bisect_right(vs, rv) - 1 if vs else -1
                    mine = _alts(initial[k] if j < 0 else hist_x[k][j])
                elif len(mine) > 1:
                    # its own write over a base a tie left open: which of the
                    # alternatives goes with which of the base's is not kept
                    unsure = True
                seen.append(mine)
            if op != READ:
                own[k] = _written(traffic, pool, op, a, b, mine)
        else:
            reads_compared += 1
            if unsure or math.prod(len(x) for x in seen) > 64:
                ambiguous += 1
                continue
            for combo in itertools.product(*seen):
                c = 0
                for v in combo:
                    c = zlib.crc32(MISSING if v is None else v, c)
                if c == crc:
                    break
            else:
                read_mismatches += 1

    readback_mismatches = 0
    if readback is not None:
        for k in range(len(initial)):
            if k in tainted:
                continue
            if readback.get(k) not in _alts(latest[k]):
                readback_mismatches += 1
        readback_mismatches += sum(1 for k in readback if not
                                   0 <= k < len(initial))
    numbers = {"readback_mismatches": readback_mismatches,
               "read_mismatches": read_mismatches,
               "conflict_violations": violations}
    notes = {"txns_replayed": n_txns, "failed_txns": failed,
             "writing_txns": len(writing),
             "reads_compared": reads_compared,
             "records_read_back": 0 if readback is None else len(readback),
             "same_version_ties": ties, "ambiguous_reads": ambiguous,
             "tainted_records": len(tainted)}
    return numbers, notes


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """`correct`, and each number compared beside its limit."""
    compared = {name: {"value": numbers[name], "limit": limits[name]}
                for name in limits}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared


# ------------------------------------------------- the reference as a system

class RefError(Exception):
    def __init__(self, name: str, retryable: bool):
        super().__init__(name)
        self.name, self.is_retryable = name, retryable


class _Turn:
    """Awaiting this hands the turn to the next actor."""

    def __await__(self):
        yield


class RefTransaction:
    def __init__(self, cluster):
        self.c = cluster
        self.reset()

    def reset(self):
        self._rv = None
        self._reads, self._writes = [], {}
        self.committed_version = None

    async def get_read_version(self) -> int:
        if self._rv is None:
            await _Turn()
            self._rv = self.c.version
        return self._rv

    async def get(self, key: bytes):
        """Read-your-writes, as `check_history` judges it: the transaction's
        own `set` of the key answers, and adds no read conflict."""
        if key in self._writes:
            return self._writes[key]
        rv = await self.get_read_version()
        await _Turn()
        self._reads.append(key)
        return self.c.read(key, rv)

    def set(self, key: bytes, value: bytes):
        self._writes[key] = value

    async def commit(self):
        if not self._writes:
            self.committed_version = self._rv or 0
            return
        slot = [None]
        self.c.pending.append((self._rv or 0, self._reads, self._writes, slot))
        while slot[0] is None:
            await _Turn()
        if isinstance(slot[0], RefError):
            raise slot[0]
        self.committed_version = slot[0]

    async def on_error(self, e):
        for _ in range(self.c.backoff_turns):
            await _Turn()
        self.reset()


class RefCluster:
    """Multi-version dict + commit batches + the conflict check, run by turns.
    `broken` names the guarantee left out: "isolation" acknowledges every
    commit without the conflict check; "durability" acknowledges one commit
    in `lose_one_in` without storing it."""

    def __init__(self, keys: list[bytes], values: list[bytes],
                 broken: str | None = None, lose_one_in: int = 97,
                 backoff_turns: int = 3):
        self.versions = {k: [0] for k in keys}
        self.values = {k: [v] for k, v in zip(keys, values)}
        self.version = 1000
        self.pending = []
        self.broken, self.lose_one_in = broken, lose_one_in
        self.backoff_turns = backoff_turns
        self.commits = 0

    def create_transaction(self) -> RefTransaction:
        return RefTransaction(self)

    def read(self, key: bytes, rv: int):
        vs = self.versions.get(key)
        if not vs:
            return None
        return self.values[key][bisect.bisect_right(vs, rv) - 1]

    def resolve_batch(self):
        """One commit batch: one version, transactions judged in order."""
        self.version += 1000
        if not self.pending:
            return
        batch, self.pending = self.pending, []
        cv = self.version
        for rv, reads, writes, slot in batch:
            if self.broken != "isolation" and any(
                    self.versions.get(k, [0])[-1] > rv for k in reads):
                slot[0] = RefError("not_committed", True)
                continue
            self.commits += 1
            slot[0] = cv
            if (self.broken == "durability"
                    and self.commits % self.lose_one_in == 0):
                continue
            for k, v in writes.items():
                vs = self.versions.setdefault(k, [])
                xs = self.values.setdefault(k, [])
                if vs and vs[-1] == cv:
                    xs[-1] = v
                else:
                    vs.append(cv)
                    xs.append(v)

    def final(self) -> dict:
        return {k: xs[-1] for k, xs in self.values.items()}


def run_by_turns(cluster: RefCluster, actors: list, batch_every: int = 4):
    """Round-robin the actor coroutines to their end; every `batch_every`
    rounds the cluster resolves one commit batch."""
    live = list(actors)
    rounds = 0
    while live:
        for co in list(live):
            try:
                co.send(None)
            except StopIteration:
                live.remove(co)
        rounds += 1
        if rounds % batch_every == 0:
            cluster.resolve_batch()
