"""What a kernel has to move and compute, from its shapes alone: the same
work whatever implements it, so a PR that replaces the sort does not make the
count stale. Kept with the benchmark; the program's `ConflictShapes` is read
from the configuration's knobs, never imported.

`conflict_step` is bandwidth-bound: it compares and merges keys, a few integer
operations a byte, and has no matrix product. Its least time is its bytes over
the chip's HBM bandwidth.
"""

import math


def conflict_shapes(config: dict) -> dict:
    k = config["knobs"]
    txns = int(k["CONFLICT_BATCH_TXNS"])
    return {"capacity": int(k["CONFLICT_STATE_CAPACITY"]), "txns": txns,
            "reads": txns * int(k["CONFLICT_BATCH_READS_PER_TXN"]),
            "writes": txns * int(k["CONFLICT_BATCH_WRITES_PER_TXN"]),
            "key_bytes": int(config.get("conflict_key_bytes", 24))}


def conflict_step_bytes(capacity: int, txns: int, reads: int, writes: int,
                        key_bytes: int) -> int:
    """One read and one write of every state array at capacity, the batch in
    and the statuses out (ops/conflict.py `conflict_step`'s documented
    layout): limbs = key_bytes/4 + 1 uint32 rows of keys; `bval` int32;
    `table` ceil(log2 capacity) + 1 int32 rows; three scalars."""
    limbs = key_bytes // 4 + 1
    levels = max(1, math.ceil(math.log2(max(capacity, 2))) + 1)
    state = 4 * capacity * (limbs + 1 + levels) + 4 + 4 + 1
    batch = (4 * limbs * 2 * (reads + writes)  # rb, re, wb, we
             + 4 * (reads + writes)            # rtxn, wtxn
             + 4 * txns + txns                 # snapshot, txn_valid
             + 4 + 1)                          # commit_version, advance_floor
    statuses = 4 * txns
    return 2 * state + batch + statuses


COSTS = {"conflict_step": (conflict_shapes, conflict_step_bytes)}
