"""One closed-loop client actor: draw a plan, run it as a transaction through
the client API until it is acknowledged, log it, start the next. The same
loop drives the program (client_worker.py) and the reference put in the
program's place (control.py), so it names no class of either: an error is
told by its `name` and `is_retryable` attributes.

The log row of a transaction is all the reference needs besides the seed:
who ran it, when, how often it was tried, the read and commit versions of the
attempt that was acknowledged, and a CRC-32 chained over the values it read.
"""

from __future__ import annotations

import time
import zlib

import numpy as np

from traffic import MISSING, READ, SET

LOG_DTYPE = np.dtype([
    ("actor", "u2"), ("seq", "u4"), ("t0", "f8"), ("t1", "f8"),
    ("attempts", "u2"), ("conflicts", "u2"), ("rv", "i8"), ("cv", "i8"),
    ("crc", "u4"), ("status", "u1"), ("writes", "u1")])
ACKNOWLEDGED, FAILED = 0, 1
# errors after which nobody knows whether the commit happened: never retried,
# because a second commit of the same plan could not be told from the first
UNKNOWN_OUTCOME = ("commit_unknown_result",)
MAX_ATTEMPTS = 1000  # as good as never answered


async def run_actor(db, traffic, keys, pool, rng, actor: int, rows: list,
                    keep_going, on_first_ack=None,
                    errors: dict | None = None) -> None:
    """Run transactions until `keep_going()` is false at a transaction's
    start. Appends one LOG_DTYPE tuple per transaction to `rows`; counts in
    `errors`, by name, every error other than `not_committed`."""
    seq = 0
    while keep_going():
        plan = traffic.plan(rng)
        has_reads = any(op != SET for op, _k, _a, _b in plan)
        writes = any(op != READ for op, _k, _a, _b in plan)
        t0 = time.monotonic()
        tr = db.create_transaction()
        attempts = conflicts = 0
        status, rv, cv, crc = FAILED, 0, 0, 0
        while True:
            attempts += 1
            try:
                crc = 0
                for op, k, a, b in plan:
                    key = keys[k]
                    if op == READ:
                        v = await tr.get(key)
                        crc = zlib.crc32(MISSING if v is None else v, crc)
                    elif op == SET:
                        tr.set(key, traffic.fresh(pool, op, a, b))
                    else:
                        v = await tr.get(key)
                        crc = zlib.crc32(MISSING if v is None else v, crc)
                        tr.set(key, traffic.modify(
                            v, traffic.fresh(pool, op, a, b), b))
                if has_reads:
                    rv = await tr.get_read_version()
                await tr.commit()
                cv = tr.committed_version or 0
                status = ACKNOWLEDGED
                break
            except Exception as e:  # noqa: BLE001 — told apart by its name
                name = getattr(e, "name", None)
                if name is None:
                    raise
                if name == "not_committed":
                    conflicts += 1
                elif errors is not None:
                    errors[name] = errors.get(name, 0) + 1
                if (name in UNKNOWN_OUTCOME or not e.is_retryable
                        or attempts >= MAX_ATTEMPTS):
                    break
                await tr.on_error(e)
        t1 = time.monotonic()
        rows.append((actor, seq, t0, t1, min(attempts, 65535),
                     min(conflicts, 65535), rv, cv, crc, status, writes))
        if seq == 0 and on_first_ack is not None:
            on_first_ack()
        seq += 1
