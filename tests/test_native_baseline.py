"""The measured CPU baseline (native/skiplist_baseline.c) must keep
building and producing sane numbers — a kernel-only cell of the benchmark
would divide by it (PERF.md §7; no cell does yet)."""

import json
import os
import subprocess

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "foundationdb_tpu", "native", "skiplist_baseline.c")


def test_skiplist_baseline_builds_and_runs(tmp_path):
    exe = str(tmp_path / "skb")
    try:
        proc = subprocess.run(["cc", "-O2", "-o", exe, SRC],
                              capture_output=True, text=True, timeout=120)
    except FileNotFoundError:
        pytest.skip("no C toolchain: cc not on PATH")
    if proc.returncode != 0:
        pytest.skip(f"no C toolchain: {proc.stderr[-200:]}")
    out = subprocess.run([exe, "500", "30"], capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout.strip())
    assert rep["txns_per_batch"] == 500 and rep["batches"] == 30
    assert rep["txns_per_sec"] > 1000
    # skipListTest's workload statistics: ~5% of txns conflict (sparse
    # ranges over a 20M keyspace, 125k-txn history window)
    assert 0.85 <= rep["committed_frac"] <= 0.999, rep


def test_skiplist_baseline_decision_parity_with_oracle(tmp_path):
    """The measured baseline must make the SAME abort decisions as the
    independent Python oracle on identical batches (VERDICT r4 weak 5): a
    subtly wrong baseline would silently skew vs_baseline. Mirrors the
    reference's own cross-check of its fast path against a naive oracle
    (SkipList.cpp:1394 miniConflictSetTest)."""
    import random
    import struct

    from foundationdb_tpu.ops.batch import TxnConflictInfo
    from foundationdb_tpu.ops.conflict_oracle import OracleConflictSet
    from foundationdb_tpu.utils.knobs import KNOBS

    exe = str(tmp_path / "skb")
    try:
        proc = subprocess.run(["cc", "-O2", "-o", exe, SRC],
                              capture_output=True, text=True, timeout=120)
    except FileNotFoundError:
        pytest.skip("no C toolchain: cc not on PATH")
    if proc.returncode != 0:
        pytest.skip(f"no C toolchain: {proc.stderr[-200:]}")

    B, T = 40, 200
    KEYSPACE = 5_000  # dense: plenty of real conflicts
    WB = 8  # window in batches
    rng = random.Random(20260730)
    batches = []
    lines = [f"{B} {T}"]
    for i in range(B):
        snapshot, now, floor = i, i + WB, i
        lines.append(f"{snapshot} {now} {floor}")
        rows = []
        for _ in range(T):
            k1, s1 = rng.randrange(KEYSPACE), 1 + rng.randrange(10)
            k2, s2 = rng.randrange(KEYSPACE), 1 + rng.randrange(10)
            rows.append((k1, s1, k2, s2))
            lines.append(f"{k1} {s1} {k2} {s2}")
        batches.append((snapshot, now, rows))
    out = subprocess.run([exe, "--parity"], input="\n".join(lines) + "\n",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    c_status = out.stdout.split()
    assert len(c_status) == B

    def setk(v):  # the baseline's 16-byte setK key layout
        return b"." * 12 + struct.pack(">I", v)

    KNOBS.set("MAX_WRITE_TRANSACTION_LIFE_VERSIONS", WB)
    oracle = OracleConflictSet()
    mismatches = []
    conflicts = 0
    for bi, (snapshot, now, rows) in enumerate(batches):
        txns = [TxnConflictInfo(
            read_snapshot=snapshot,
            read_ranges=[(setk(k1), setk(k1 + s1))],
            write_ranges=[(setk(k2), setk(k2 + s2))])
            for k1, s1, k2, s2 in rows]
        want = oracle.detect(txns, now)
        got = [int(ch) for ch in c_status[bi]]
        conflicts += sum(1 for s in got if s == 0)
        for j, (w, g) in enumerate(zip(want, got)):
            if w != g:
                mismatches.append((bi, j, w, g, rows[j]))
    assert not mismatches, \
        f"{len(mismatches)} decision mismatches, first 5: {mismatches[:5]}"
    assert conflicts > 50, f"workload produced too few conflicts ({conflicts})"
