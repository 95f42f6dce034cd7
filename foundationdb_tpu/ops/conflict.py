"""The TPU conflict engine: batched MVCC conflict detection in one XLA launch.

This replaces fdbserver/SkipList.cpp (the reference's hand-tuned CPU conflict
engine, SURVEY.md §3.2) with a TPU-first design. The reference hides memory
latency with 16 interleaved skiplist cursors (SkipList.cpp:526-552) and a
hierarchical bitmask (:1028-1130); we instead make the whole batch a dense
tensor program:

State = the *max-commit-version step function* over the keyspace, stored as
device-resident sorted boundary keys (fixed-width uint32 limbs) + per-segment
version offsets + a sparse-table (power-of-two window) max pyramid — the dense
analogue of the skiplist's per-level max-version annotations (:324-357).

detect = ONE jitted function built around ONE order of
[state boundaries | read begins | read ends | write begins | write ends].
The state's boundaries are kept sorted from step to step, so the order is
built, not found: the batch's endpoints alone are sorted, each is ranked in
the state by a fixed-trip bisection, and the two sorted runs are interleaved
by those ranks (_merged_order):
  1. too-old filter (SkipList.cpp:985 semantics)
  2. history check: each read endpoint's rank among state boundaries comes
     from that order; O(1) sparse-table range-max over the segment versions,
     compare against each txn's read snapshot (replaces CheckMax :755-837)
  3. intra-batch: endpoint ranks from the same order feed a dyadic
     sort/scan evaluator for "earlier txns win" semantics — each fixpoint
     sweep is O(n log n) prefix scans over per-level sorted write endpoints
     instead of the old dense (NW, NR) overlap matrix mat-vec, and the
     sweep count is statically bounded (a lax.scan with an early-out cond,
     never an unbounded while_loop); unconverged batches fall back to an
     exact host-side pass (replaces MiniConflictSet :1028-1130; see
     docs/conflict_kernel.md)
  4. merge of surviving writes into the step function: the ordered array IS
     the union; slots, coverage, and values are carved out with prefix scans
     and one compaction scatter (replaces mergeWriteConflictRanges :1260)
  5. window GC by clamp + coalesce (replaces removeBefore :665)

Versions on device are int32 *offsets* from a host-kept int64 base (the MVCC
window is only 5e6 versions wide — fdbserver/Knobs.cpp:30-34 — so offsets fit
comfortably; the host rebases long before overflow). This keeps the kernel in
TPU-native 32-bit arithmetic.

Keys are exact up to KEY_BYTES (24) bytes; longer keys collapse to their
prefix, which can only create false conflicts (safe), never false commits
(utils/keys.py).
"""

from __future__ import annotations

import functools
import json
import os
import re
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from foundationdb_tpu.ops.batch import COMMITTED, CONFLICT, TOO_OLD, TxnConflictInfo
from foundationdb_tpu.utils import jaxenv
from foundationdb_tpu.utils import keys as keylib
from foundationdb_tpu.utils import trace
from foundationdb_tpu.utils.errors import FDBError
from foundationdb_tpu.utils.knobs import KNOBS
from foundationdb_tpu.utils.stats import CounterCollection
from foundationdb_tpu.utils.trace import g_trace_batch

# Process-wide device-kernel gauges (merged into RESOLVER_METRICS):
# dispatch count from detect_async_impl, chunks that DetectHandle.result
# finished with the exact host intra-batch pass, readback-wait wall seconds
# from drain_and_collect (perf_counter — wall time by design: the wait
# happens off-loop, where sim virtual time does not advance).
kernel_metrics = CounterCollection("ConflictKernel")
_kernel_dispatches = kernel_metrics.counter("KernelDispatches")
_host_exact_chunks = kernel_metrics.counter("HostExactChunks")
_readback_waits = kernel_metrics.counter("ReadbackWaits")
_readback_wait_seconds = kernel_metrics.counter("ReadbackWaitSeconds")
# JAX's own persistent-compile-cache events: a hit is a program read back
# from the cache directory, a miss one compiled here and written to it
# (programs under the cache's compile-time threshold are neither).
_persistent_cache = {
    "/jax/compilation_cache/cache_hits":
        kernel_metrics.counter("PersistentCacheHits"),
    "/jax/compilation_cache/cache_misses":
        kernel_metrics.counter("PersistentCacheMisses"),
}


def _on_jax_event(event: str, **_kw) -> None:
    counter = _persistent_cache.get(event)
    if counter is not None:
        counter.increment()


jax.monitoring.register_event_listener(_on_jax_event)


def compile_cache_stats() -> dict:
    """In-process hits/misses of the jitted step (one miss per distinct
    program this process asked for)."""
    step = _compiled_step.cache_info()
    return {"CompileCacheHits": step.hits, "CompileCacheMisses": step.misses}

L = keylib.NUM_LIMBS  # default key limbs (6 data + 1 length; see ConflictShapes.key_bytes)
_NEG_INT = -(1 << 30)
# "no version" sentinel, below any clamped offset. A plain host int on
# purpose: a module-level jnp scalar would initialize the device backend at
# IMPORT time, which every server role (and any tool importing the client
# stack) would pay — and a process that merely imports this module would
# take the chip from the one that serves with it.
# jnp expressions promote it exactly like the former device constant.
NEG = _NEG_INT
_REBASE_THRESHOLD = 1 << 29
# host encode buffers kept per shape bucket (BatchEncoder._buffers): one being
# encoded, up to three whose transfer or step may still be reading them
ENCODE_RING = 4
# the named_scopes inside conflict_step, in program order (what a profile's
# operations are grouped by; docs/observability.md has the table)
SCOPES = ("sort", "history", "intra", "merge", "gc", "table")


def _profiler_annotation(span: str, ident: str, mono_us: int):
    return jax.profiler.TraceAnnotation(span, id=ident, mono_us=mono_us)


_HLO_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?%([\w.\-]+) = .*\bop_name="([^"]*)"', re.MULTILINE)


def scope_map(hlo_text: str, scopes=SCOPES) -> dict[str, str]:
    """{instruction: scope} for every instruction of a compiled module's
    text whose op_name passes through one of `scopes`."""
    out = {}
    for name, op_name in _HLO_INSTRUCTION.findall(hlo_text):
        scope = next((p for p in op_name.split("/") if p in scopes), None)
        if scope is not None:
            out[name] = scope
    return out


def write_scope_map(directory: str, shapes, compiled_text: str,
                    scopes=SCOPES) -> None:
    """`scopes.conflict_step.<reads>x<writes>.json` of one compiled step
    program: {"scopes": {instruction: scope}}."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(
        directory, f"scopes.conflict_step.{shapes.reads}x{shapes.writes}.json")
    with open(path, "w") as f:
        json.dump({"scopes": scope_map(compiled_text, scopes)}, f)


def install_profiler_annotator() -> None:
    """Put utils/trace's sections on the profiler's timeline. Called by the
    device engines' constructors: only a process that builds one has a chip
    to profile, and utils/trace itself stays off JAX."""
    trace.set_annotator(_profiler_annotation)


def _bulk_encode(keys: list[bytes], out: np.ndarray, *, round_up: bool):
    """Encode keys into out[:, :len(keys)] (SoA limbs), C path if built.
    The limb count (and so the key width) comes from `out`'s shape."""
    if not keys:
        return
    from foundationdb_tpu import native

    nl = out.shape[0]
    key_bytes = (nl - 1) * 4
    if native.available():
        tmp = np.empty((nl, len(keys)), dtype=np.uint32)
        native.mod.encode_keys_into(keys, tmp, round_up, key_bytes)
        out[:, : len(keys)] = tmp
    else:
        buf = np.zeros(nl, dtype=np.uint32)
        for i, k in enumerate(keys):
            keylib.encode_key(k, buf, round_up=round_up, key_bytes=key_bytes)
            out[:, i] = buf


# ---------------------------------------------------------------------------
# multi-limb key comparisons (vectorized lexicographic)
# ---------------------------------------------------------------------------

def _key_lt(a, b):
    """a < b lexicographically; a, b are (L, ...) uint32."""
    lt = jnp.zeros(a.shape[1:], dtype=bool)
    eq = jnp.ones(a.shape[1:], dtype=bool)
    for i in range(a.shape[0]):
        lt = lt | (eq & (a[i] < b[i]))
        eq = eq & (a[i] == b[i])
    return lt


def _key_eq(a, b):
    eq = jnp.ones(a.shape[1:], dtype=bool)
    for i in range(a.shape[0]):
        eq = eq & (a[i] == b[i])
    return eq


def _lex_sort_perm(keys):
    """Permutation that sorts the columns of `keys` ((NK, N), row 0 most
    significant) lexicographically, equal columns in index order — exactly
    what one stable lax.sort over NK key operands hands an iota payload.

    Built as NK stable single-key passes from the least significant row up
    (LSD order) inside a fori_loop, so the compiler builds ONE two-operand
    sort whatever NK is: the TPU compiler's time for a variadic sort grows
    with both its operand and its key count (minutes for the step's former
    10-operand, 8-key sort; PERF.md "compile times")."""
    nk, n = keys.shape

    def one_pass(i, perm):
        row = keys[nk - 1 - i]
        return lax.sort([row[perm], perm], num_keys=1, is_stable=True)[1]

    return lax.fori_loop(0, nk, one_pass, jnp.arange(n, dtype=jnp.int32))


def _rank_in_sorted(skeys, q, strict):
    """For each column of `q` ((NK, M)): how many columns of `skeys`
    ((NK, K), non-decreasing) precede it — those < q where `strict`, those
    <= q elsewhere (lower / upper bound; one search serves both).

    A fixed-trip bisection: ceil(log2(K + 1)) rounds in a fori_loop (a scan
    in the jaxpr, never an unbounded while), one (NK, M) column gather a
    round. A query whose interval has closed rides the remaining rounds
    unchanged."""
    K = skeys.shape[1]
    m = q.shape[1]

    def one_round(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        km = skeys[:, jnp.minimum(mid, K - 1)]
        before = jnp.where(strict, _key_lt(km, q), ~_key_lt(q, km))
        go = lo < hi
        return (jnp.where(go & before, mid + 1, lo),
                jnp.where(go & ~before, mid, hi))

    lo, _hi = lax.fori_loop(
        0, int(K).bit_length(), one_round,
        (jnp.zeros(m, jnp.int32), jnp.full(m, K, jnp.int32)))
    return lo


def _merged_order(bkeys, bk, bcls):
    """The stable sort of [state | batch] by (key, class, index), built
    without sorting the state: `bkeys` ((NK, K)) is already non-decreasing
    and all of class 1, so only the M batch columns `bk` (classes `bcls`,
    0 or 2) are sorted, each is ranked in the state, and the two sorted runs
    are interleaved by those ranks. Element for element what
    _lex_sort_perm([bkeys | bk] + class row) returns:

      sidx    (K + M,) original index of the element at each sorted position
      bpos    (M,)     sorted position of each batch element (the inverse
                       permutation past the state's K entries)

    and of the sorted batch, M wide: `bperm` the element at each place, `q`
    their keys, `rank` the state keys before each, `p` its sorted position.

    Ties: equal batch elements keep index order (the M-wide sort is stable);
    class 0 ranks by lower bound and so lands before equal state keys,
    class 2 by upper bound and lands after them; the state's own equal keys
    (its all-ones padding) keep slot order."""
    K = bkeys.shape[1]
    M = bk.shape[1]
    bperm = _lex_sort_perm(
        jnp.concatenate([bk, bcls.astype(jnp.uint32)[None]]))
    q = bk[:, bperm]
    rank = _rank_in_sorted(bkeys, q, bcls[bperm] == 0)
    # sorted batch element j has `rank` state keys and j batch elements
    # before it: strictly increasing positions
    p = rank + jnp.arange(M, dtype=jnp.int32)
    bpos = jnp.zeros(M, jnp.int32).at[bperm].set(p, unique_indices=True)
    src_b = jnp.full(K + M, -1, jnp.int32).at[p].set(
        K + bperm, indices_are_sorted=True, unique_indices=True)
    is_batch = src_b >= 0
    # a state slot keeps its index less the batch elements before it
    sidx = jnp.where(
        is_batch, src_b, jnp.arange(K + M, dtype=jnp.int32)
        - jnp.cumsum(is_batch.astype(jnp.int32)))
    return sidx, bpos, bperm, q, rank, p


def _batch_key_ranks(q, bperm):
    """Which sorted batch keys differ from the one before, and each batch
    element's rank: the count of distinct batch keys below its own."""
    q_new = jnp.concatenate(
        [jnp.ones(1, bool), ~_key_eq(q[:, 1:], q[:, :-1])])
    return q_new, jnp.zeros(q.shape[1], jnp.int32).at[bperm].set(
        jnp.cumsum(q_new.astype(jnp.int32)) - 1, unique_indices=True)


def _group_starts(bkeys, nb, sidx, q, q_new, rank, p):
    """(K + M,) bool: the key at each merged position differs from the one
    before it, without the keys in that order. Live state keys are distinct
    and below the padding: behind a state row, slot s opens a group iff
    s <= nb; only a batch row and the state row behind one compare keys."""
    K = bkeys.shape[1]
    pad_opens = ~jnp.all(  # (only a raw batch can make the padding's key live)
        bkeys[:, jnp.maximum(nb - 1, 0)] == jnp.uint32(0xFFFFFFFF))
    newgrp = (sidx < nb) | ((sidx == nb) & pad_opens)
    # before a batch row: the one it shares a rank with, else slot rank - 1
    same = jnp.concatenate([jnp.zeros(1, bool), rank[1:] == rank[:-1]])
    b_open = jnp.where(
        same, q_new,
        (rank == 0) | ~_key_eq(q, bkeys[:, jnp.maximum(rank - 1, 0)]))
    # state slot `rank` comes right behind the last batch row of that rank
    last = (rank < K) & ~jnp.concatenate([same[1:], jnp.zeros(1, bool)])
    s_open = ~_key_eq(q, bkeys[:, jnp.minimum(rank, K - 1)])
    return newgrp.at[p].set(
        b_open, indices_are_sorted=True, unique_indices=True
    ).at[jnp.where(last, p + 1, p)].set(jnp.where(last, s_open, b_open))


# ---------------------------------------------------------------------------
# sparse table (range-max in O(1) per query)
# ---------------------------------------------------------------------------

def _build_table(vals):
    """vals: (K,) int32 -> (LEVELS, K) power-of-two window maxima.

    table[l, i] = max(vals[i : i + 2**l]) (clipped at K). The dense analogue
    of the skiplist's level max-version pyramid (SkipList.cpp:324-357).
    """
    K = vals.shape[0]
    levels = max(1, int(np.ceil(np.log2(max(K, 2)))) + 1)
    rows = [vals]
    cur = vals
    for l in range(1, levels):
        shift = 1 << (l - 1)
        shifted = jnp.concatenate([cur[shift:], jnp.full(min(shift, K), NEG, cur.dtype)])[:K]
        cur = jnp.maximum(cur, shifted)
        rows.append(cur)
    return jnp.stack(rows)


def _range_max(table, i0, i1):
    """Max over vals[i0:i1) for vectors i0 < i1 (int32 arrays)."""
    w = jnp.maximum(i1 - i0, 1)
    lvl = 31 - lax.clz(w)  # floor(log2(w))
    left = table[lvl, i0]
    right = table[lvl, jnp.maximum(i1 - (1 << lvl).astype(jnp.int32), i0)]
    return jnp.maximum(left, right)


# ---------------------------------------------------------------------------
# the jitted step
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ConflictShapes:
    """Static shapes of one conflict batch (one XLA program per instance).

    `key_bytes` sets the exact-comparison width (keys longer than it collapse
    conservatively onto their prefix, utils/keys.py): compare cost on device
    scales linearly with the limb count, so clusters with bounded keys run a
    narrower engine — the reference's memcmp cost scales with key length the
    same way (SkipList.cpp getCharacter/compare)."""

    capacity: int  # K: boundary slots in the step function
    txns: int  # T
    reads: int  # NR: total read ranges per batch (flattened)
    writes: int  # NW: total write ranges per batch
    key_bytes: int = keylib.KEY_BYTES

    def __post_init__(self):
        if self.key_bytes % 4 or not 4 <= self.key_bytes <= 64:
            raise ValueError(
                f"key_bytes must be a multiple of 4 in [4, 64], got "
                f"{self.key_bytes} (the limb encoding is 4 bytes wide and "
                f"the native encoder caps at 64)")

    @property
    def limbs(self) -> int:
        return self.key_bytes // 4 + 1


def _carry_last_flagged(values, flags):
    """At each position: `values` at the most recent position with flags=True
    (inclusive), or the dtype value passed at unflagged position 0 if none yet.
    One associative scan (the 'last valid' monoid)."""
    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av), af | bf
    out, _ = lax.associative_scan(op, (values, flags))
    return out


def _seg_cummax(vals, reset):
    """Inclusive running max of `vals` restarting wherever reset=True
    (segmented cummax; one associative scan — the monoid carries whether a
    segment boundary was crossed)."""
    def op(a, b):
        av, ar = a
        bv, br = b
        return jnp.where(br, bv, jnp.maximum(av, bv)), ar | br
    out, _ = lax.associative_scan(op, (vals, reset))
    return out


# ---------------------------------------------------------------------------
# intra-batch scan evaluator (dyadic decomposition over the txn index)
# ---------------------------------------------------------------------------

def _intra_scan_levels(T, wtxn_c, rtxn, rbr, rer, wbr, wer):
    """Sweep-invariant geometry for the scan intra-batch evaluator.

    One level per power-of-two block size 2^l (l < ceil(log2 T)). At level l
    writes sort by (wtxn >> l, wbr); a read of txn t queries block
    (t >> l) - 1, i.e. the aligned block of 2^l transactions immediately
    before t's block. The union of those query blocks over all levels is
    exactly [0, t) — the canonical dyadic prefix — so "some committed
    EARLIER txn's write overlaps this read" decomposes into per-level
    queries whose candidates are contiguous runs of the level-sorted order:

      case A (write begins strictly inside the read): a prefix-sum count
        between the two query positions;
      case B (write begins at-or-before the read's begin and covers it): a
        block-segmented running max of committed write ends, gathered at the
        first query position.

    Query positions ride the same per-level sort as two query elements per
    read (class keys order them against equal write begins so <= / < fall
    out of the element order), so the geometry costs one sort + one
    inverse-permutation scatter per level PER STEP and is reused by every
    fixpoint sweep. A read of txn 0 gets query block -1, which sorts before
    every write and self-masks; padding reads/writes are masked by the
    validity masks the caller folds into the committed-write vector.
    """
    NW = wbr.shape[0]
    NR = rbr.shape[0]
    M = NW + 2 * NR
    n_levels = max(1, int(T - 1).bit_length())
    arange_m = jnp.arange(M, dtype=jnp.int32)
    # class tiebreak at equal (block, rank): hi-query(-1) < write(0) <
    # lo-query(1) => lo counts wbr <= rbr, hi counts wbr < rer
    cls = jnp.concatenate([
        jnp.zeros(NW, jnp.int32), jnp.ones(NR, jnp.int32),
        jnp.full(NR, -1, jnp.int32)])
    key2 = jnp.concatenate([wbr, rbr, rer])
    qblk0 = rtxn  # block keys are recomputed per level from the txn index
    levels = []
    for l in range(n_levels):
        key1 = jnp.concatenate(
            [wtxn_c >> l, (qblk0 >> l) - 1, (qblk0 >> l) - 1])
        s1, _s2, _scl, si = lax.sort([key1, key2, cls, arange_m], num_keys=3)
        inv = jnp.zeros(M, jnp.int32).at[si].set(arange_m)
        is_w = si < NW
        src = jnp.minimum(si, NW - 1)
        werl = jnp.where(is_w, wer[src], -1)
        bnd = jnp.concatenate([jnp.ones(1, bool), s1[1:] != s1[:-1]])
        levels.append((src, is_w, werl, bnd,
                       inv[NW:NW + NR], inv[NW + NR:]))
    return levels


def _intra_scan_blocked(c_w, levels, rbr):
    """blocked_r[j] = some write with c_w=True belonging to an earlier txn
    overlaps read j. `c_w` is the (NW,) committed∧valid∧nonempty write mask;
    exactness matches the dense overlap-matrix formulation element for
    element (same ranks, same strict earlier-txn order)."""
    NR = rbr.shape[0]
    blocked = jnp.zeros(NR, bool)
    for src, is_w, werl, bnd, qlo, qhi in levels:
        cm = is_w & c_w[src]
        pref = jnp.cumsum(cm.astype(jnp.int32))  # queries contribute 0
        count_a = pref[qhi] - pref[qlo]
        segmax = _seg_cummax(jnp.where(cm, werl, -1), bnd)
        blocked = blocked | (count_a > 0) | (segmax[qlo] > rbr)
    return blocked


def _run_sandwich(f, g, rounds: int):
    """Statically-bounded lower/upper sandwich on the antitone map f.

    upper ⊇ truth ⊇ lower is invariant; each round tightens both by one
    dependency depth from each side, and rounds are skipped via lax.cond
    once the bounds pinch (so runtime tracks the batch's ACTUAL chain depth,
    but the trip count — hence the jaxpr — is bounded). rounds >= T//2 guarantees convergence for any batch; smaller
    bounds report converged=False and the host wrapper finishes those txns
    exactly (DetectHandle.result). Returns (lower, upper, converged)."""
    upper = g
    lower = f(upper)

    def round_fn(lu, _):
        def go(lu):
            lo, up = lu
            up2 = f(lo)
            return f(up2), up2
        lu2 = lax.cond(jnp.all(lu[0] == lu[1]), lambda x: x, go, lu)
        return lu2, None

    (lower, upper), _ = lax.scan(round_fn, (lower, upper), None,
                                 length=max(rounds, 0))
    return lower, upper, jnp.all(lower == upper)


def _auto_rounds(T: int) -> int:
    """Default sandwich bound: full-convergence for small batches (T//2+1
    rounds make any chain depth exact), capped at 32 for large ones — a
    depth-65 dependency chain inside one chunk is adversarial, and those
    batches still get exact statuses from the host fallback."""
    return min(T // 2 + 1, 32)


def conflict_step(state: dict, batch: dict, *, shapes: ConflictShapes,
                  max_write_life: int, intra_rounds: int = 0):
    """Pure function: (state, batch) -> (state', statuses, info). Jit-able.

    intra_rounds bounds the intra-batch evaluator's sandwich rounds (0 =
    auto, see _auto_rounds).

    state:
      bkeys (L,K) uint32: nb live boundaries, DISTINCT and increasing, then
      all-0xFFFFFFFF padding (keys.MAX_LIMBS, above any encoded key);
      bval (K,) i32; nb () i32; oldest () i32; table (LEVELS,K) i32
    batch:
      txn_valid (T,) bool; snapshot (T,) i32 (version offsets)
      rb, re (L,NR) u32; rtxn (NR,) i32 (= T for padding);
      wb, we (L,NW) u32; wtxn (NW,) i32 (= T for padding)
      commit_version () i32 offset
      advance_floor () bool — advance the MVCC window after this chunk
      (False for all but the last chunk of a logical batch)

    Layout: ONE order of [state boundaries | rb | re | wb | we] per step
    feeds everything — history positions, intra-batch endpoint ranks and the
    merged union of state with committed write endpoints. It is the stable
    sort by (key, class, index), constructed (_merged_order) from the batch's
    M = 2NR + 2NW rows: init_state, the gc scope, the poison branch,
    rebase_state and the sharded engine's re-cut all leave `bkeys` as above.
    The keys are never gathered into it: key groups and ranks come from the
    sorted batch (_group_starts, _batch_key_ranks; PERF.md, PR 31).
    """
    T, NR, NW, K = shapes.txns, shapes.reads, shapes.writes, shapes.capacity
    bkeys, bval, oldest, table = (
        state["bkeys"], state["bval"], state["oldest"], state["table"])
    rb, re, rtxn = batch["rb"], batch["re"], batch["rtxn"]
    wb, we, wtxn = batch["wb"], batch["we"], batch["wtxn"]
    snapshot, txn_valid = batch["snapshot"], batch["txn_valid"]

    # The numbered phases below run inside jax.named_scope, which names the
    # phase in every operation's metadata and changes nothing else: a
    # profile of the step program reads its device time by phase (SCOPES).
    with jax.named_scope("history"):
        # a slot's transaction number says whether it is used, so an
        # empty-but-real range (b == e) still counts as "has reads" for the
        # too-old rule
        rvalid = rtxn < T
        wvalid = wtxn < T
        has_reads = (jnp.zeros(T + 1, bool).at[rtxn].max(rvalid))[:T]

    with jax.named_scope("sort"):
        # ---- 0. THE order of [state | rb | re | wb | we] ----
        # Class tiebreak at equal keys: re(0) < state(1) < rb/wb/we(2).
        #  - rb after equal state keys  -> #state<=rb = upper bound (segment of rb)
        #  - re before equal state keys -> #state<re  = lower bound
        #  - wb/we after equal state keys -> duplicate endpoint lands in the SAME
        #    union slot as the state boundary it equals
        M = 2 * NR + 2 * NW
        bk = jnp.concatenate([rb, re, wb, we], axis=1)  # (L, M)
        bcls = jnp.concatenate([
            jnp.full(NR, 2, jnp.int32), jnp.zeros(NR, jnp.int32),
            jnp.full(2 * NW, 2, jnp.int32)])
        sidx, spos_b, bperm, q, rank, p = _merged_order(bkeys, bk, bcls)
        # the state keys before a batch row ARE its bound in the state
        rank_b = jnp.zeros(M, jnp.int32).at[bperm].set(
            rank, unique_indices=True)
        # state values in sorted order (a batch row's is masked where read)
        sval = bval[jnp.minimum(sidx, K - 1)]

    with jax.named_scope("history"):
        # ---- 1. too-old (only txns with read ranges expire: SkipList.cpp:985) ----
        too_old = txn_valid & has_reads & (snapshot < oldest)

        # ---- 2. history check: range-max of step function vs snapshot ----
        ub_rb = rank_b[:NR]        # #state keys <= rb
        lb_re = rank_b[NR:2 * NR]  # #state keys < re
        i0 = jnp.maximum(ub_rb - 1, 0)  # segment containing begin
        i1 = lb_re  # first boundary >= end
        nonempty = _key_lt(rb, re)
        maxver = _range_max(table, i0, jnp.maximum(i1, i0 + 1))
        rsnap = snapshot[jnp.minimum(rtxn, T - 1)]
        read_hits = rvalid & nonempty & (maxver > rsnap)
        hist_conflict = (jnp.zeros(T + 1, bool).at[rtxn].max(read_hits))[:T]

        g = txn_valid & ~too_old & ~hist_conflict
    with jax.named_scope("intra"):
        # ---- 3. intra-batch: endpoint ranks -> overlap queries -> fixpoint ----
        # An endpoint's rank = the number of distinct batch-endpoint keys
        # below it: order-isomorphic to the keys over batch endpoints. Each
        # sweep's "does a committed earlier txn's write overlap this read" is
        # answered with per-level prefix scans over sorted write endpoints
        # (geometry built once per step, _intra_scan_levels) — O(n log n) per
        # sweep with no n×n matrix materialized.
        q_new, qranks = _batch_key_ranks(q, bperm)  # of [rb | re | wb | we]
        rbr, rer = qranks[:NR], qranks[NR:2 * NR]
        wbr, wer = qranks[2 * NR:2 * NR + NW], qranks[2 * NR + NW:]

        # empty/inverted ranges (end <= begin) participate in neither side;
        # strict wtxn < rtxn = "earlier txns win" (checkIntraBatchConflicts
        # SkipList.cpp:1139-1152 processes in batch order)
        wtxn_c = jnp.minimum(wtxn, T - 1)
        r_ok = rvalid & (rbr < rer)
        w_ok = wvalid & (wbr < wer)
        levels = _intra_scan_levels(T, wtxn_c, rtxn, rbr, rer, wbr, wer)

        def _f_commit(c):
            """f(c)[t] = g[t] and no committed-in-c earlier txn's write
            overlaps any of t's reads."""
            blocked_r = _intra_scan_blocked(c[wtxn_c] & w_ok, levels, rbr) & r_ok
            return g & ~(jnp.zeros(T + 1, bool).at[rtxn].max(blocked_r))[:T]

        rounds = intra_rounds if intra_rounds > 0 else _auto_rounds(T)
        # statuses come from `lower` (⊆ truth: never a false commit) and the
        # merge uses `upper` (⊇ truth: never a missing write in history);
        # both are the truth itself whenever converged — always, for
        # rounds >= T//2+1
        commit, merge_commit, converged = _run_sandwich(_f_commit, g, rounds)

        statuses = jnp.where(
            commit, COMMITTED,
            jnp.where(too_old, TOO_OLD, CONFLICT)).astype(jnp.int32)
        statuses = jnp.where(txn_valid, statuses, COMMITTED)
    return _merge_phase(state, batch, statuses, commit, shapes,
                        max_write_life, sort_products=(
                            sval, sidx, spos_b, q, q_new, rank, p),
                        merge_commit=merge_commit, converged=converged,
                        eligible=g)


def _merge_phase(state, batch, statuses, commit, shapes, max_write_life,
                 sort_products, merge_commit, converged, eligible):
    T, NR, NW, K = shapes.txns, shapes.reads, shapes.writes, shapes.capacity
    L = shapes.limbs
    bkeys, nb, oldest = state["bkeys"], state["nb"], state["oldest"]
    wb, we, wtxn = batch["wb"], batch["we"], batch["wtxn"]
    vnew = batch["commit_version"]
    wvalid = wtxn < T
    wtxn_c = jnp.minimum(wtxn, T - 1)

    with jax.named_scope("merge"):
        # ---- 4. merge surviving writes into the step function at vnew ----
        # The union of state boundaries and committed write endpoints is already
        # IN the ordered array (sort_products); dead elements — read
        # endpoints, uncommitted/empty writes, dead state slots — are simply not
        # union slots, and the merged state is carved out with prefix scans + one
        # compaction scatter: no second positioning of the writes in the state,
        # the history and intra-batch checks already paid for the order (the
        # device analogue of the reference's finger-merge,
        # mergeWriteConflictRanges SkipList.cpp:1260).
        sval, sidx, spos_b, q, q_new, rank, p = sort_products
        commit_w = merge_commit[wtxn_c]
        # committed, non-empty writes only: an inverted range would inject a
        # reversed -1/+1 coverage delta and cancel other writes' coverage
        cw = wvalid & commit_w & _key_lt(wb, we)
        # coverage deltas at each write endpoint's sorted position: +1 at
        # committed begins, -1 at committed ends (positions are unique)
        delta_w = jnp.concatenate([cw.astype(jnp.int32), -(cw.astype(jnp.int32))])
        pos_w = spos_b[2 * NR:]
        delta_sorted = jnp.zeros_like(sidx).at[pos_w].set(delta_w)

        # union slot sources: live state boundaries + committed write endpoints
        live_state = sidx < nb  # a batch row's index is K or more
        is_src = live_state | (delta_sorted != 0)
        # one representative (slot) per distinct key among sources; the class
        # tiebreak sorted state before equal write endpoints, so a duplicate
        # endpoint joins the state boundary's slot
        newgrp = _group_starts(bkeys, nb, sidx, q, q_new, rank, p)
        cum_src_excl = jnp.cumsum(is_src.astype(jnp.int32)) - is_src
        grp_start_src = lax.cummax(jnp.where(newgrp, cum_src_excl, -1))
        rep = is_src & (cum_src_excl == grp_start_src)

        # value of each slot under the CURRENT step function: the last live
        # state boundary's at-or-before it, carried forward by a scan over
        # sval (cheaper than a random bval gather per slot)
        val_u = _carry_last_flagged(jnp.where(live_state, sval, NEG), live_state)

        # coverage at a slot = total delta through the END of its key group
        # (within a group the +1/-1 order is arbitrary; at the group end it has
        # settled). Backward-carry the group-end prefix sum to every member.
        csum_delta = jnp.cumsum(delta_sorted)
        grp_last = jnp.concatenate([newgrp[1:], jnp.ones(1, bool)])
        cover_cnt = jnp.flip(_carry_last_flagged(
            jnp.flip(jnp.where(grp_last, csum_delta, 0)), jnp.flip(grp_last)))
        cover = cover_cnt > 0
        newval = jnp.where(cover, jnp.maximum(val_u, vnew), val_u)

    with jax.named_scope("gc"):
        # ---- 5. window GC: clamp to new floor + coalesce equal neighbors ----
        # advance_floor is False for all but the last chunk of a logical batch:
        # the too-old check and history clamping must use the PRE-batch floor for
        # every transaction of the batch (the reference advances oldestVersion
        # once per detectConflicts call, SkipList.cpp:1199-1206).
        floor = jnp.where(batch["advance_floor"],
                          vnew - jnp.int32(max_write_life), oldest)
        new_oldest = jnp.maximum(oldest, floor)
        newval = jnp.maximum(newval, new_oldest)

        # coalesce (removeBefore's segment-merge analogue): a slot is redundant
        # if its value equals its predecessor slot's post-clamp value
        cum_rep = jnp.cumsum(rep.astype(jnp.int32))
        rep_val_carried = _carry_last_flagged(jnp.where(rep, newval, NEG), rep)
        prev_rep_val = jnp.concatenate(
            [jnp.full(1, NEG, jnp.int32), rep_val_carried[:-1]])
        keep2 = rep & ((cum_rep == 1) | (newval != prev_rep_val))
        n2 = jnp.sum(keep2.astype(jnp.int32))
        # rows the window drops this step: union slots (the state's live
        # boundaries and the new ones) the kept-mask does not keep
        evicted = cum_rep[-1] - n2
        # compact kept slots to the front: each kept row's element index and
        # value scattered to its slot (slot K takes the rest), the state's
        # keys gathered through the indices, the new boundaries' (<= 2NW
        # committed write endpoints) written over their slots further down
        cpos = jnp.cumsum(keep2.astype(jnp.int32)) - 1
        cpos = jnp.where(keep2, jnp.minimum(cpos, K - 1), K)
        csrc = jnp.full(K + 1, -1, jnp.int32).at[cpos].set(sidx)[:K]
        out_vals = jnp.full(K + 1, NEG, jnp.int32).at[cpos].set(newval)[:K]
        out_keys = jnp.where((csrc >= 0)[None, :],
                             bkeys[:, jnp.clip(csrc, 0, K - 1)],
                             jnp.uint32(0xFFFFFFFF))

        overflow = n2 > K

        # Overflow poisons the state (sticky): truncation would drop the
        # highest-key history segments and cause FALSE COMMITS for batches
        # already enqueued behind this one (detect_async pipelines without a
        # host sync). Instead the whole keyspace collapses to one segment at
        # vnew, so every later stale read conflicts — conservative-only — until
        # the owner sees info["overflow"] and reconstructs (clearConflictSet
        # semantics, SkipList.cpp:957). This batch's own statuses are computed
        # pre-merge and remain exact.
        poisoned = state["poisoned"] | overflow
        pois_keys = jnp.full((L, K), jnp.uint32(0xFFFFFFFF)).at[:, 0].set(
            jnp.zeros(L, dtype=jnp.uint32))  # encode(b"") == all-zero limbs
        pois_vals = jnp.full(K, NEG, jnp.int32).at[0].set(vnew)
        out_keys = jnp.where(poisoned, pois_keys, out_keys)
        out_keys = out_keys.at[
            :, jnp.where(poisoned, K, cpos[spos_b[2 * NR:]])
        ].set(jnp.concatenate([wb, we], axis=1), mode="drop")
        out_vals = jnp.where(poisoned, pois_vals, out_vals)
        n2 = jnp.where(poisoned, 1, n2)
    with jax.named_scope("table"):
        new_table = _build_table(out_vals)

    new_state = {
        "bkeys": out_keys,
        "bval": out_vals,
        "nb": jnp.minimum(n2, K).astype(jnp.int32),
        "oldest": new_oldest.astype(jnp.int32),
        "table": new_table,
        "poisoned": poisoned,
    }
    info = {"overflow": poisoned, "boundaries": n2,
            "evicted": jnp.where(poisoned, 0, evicted),
            "committed": jnp.sum(commit.astype(jnp.int32)),
            "converged": converged, "eligible": eligible}
    return new_state, statuses, info


def rebase_state(state: dict, delta: int):
    """Shift all version offsets down by delta (host rebases the int64 base)."""
    d = jnp.int32(delta)
    bval = jnp.maximum(state["bval"] - d, NEG)
    return {
        "bkeys": state["bkeys"],
        "bval": bval,
        "nb": state["nb"],
        "oldest": jnp.maximum(state["oldest"] - d, NEG),
        "table": _build_table(bval),
        "poisoned": state["poisoned"],
    }


def init_state(shapes: ConflictShapes, oldest: int = 0):
    K = shapes.capacity
    L = shapes.limbs
    maxk = np.full((L, K), 0xFFFFFFFF, dtype=np.uint32)
    maxk[:, 0] = 0  # segment 0: [b"" (all-zero limbs), next) -> NEG
    bval = np.full(K, int(NEG), dtype=np.int32)
    return {
        "bkeys": jnp.asarray(maxk),
        "bval": jnp.asarray(bval),
        "nb": jnp.int32(1),
        "oldest": jnp.int32(oldest),
        "table": _build_table(jnp.asarray(bval)),
        "poisoned": jnp.asarray(False),
    }


# ---------------------------------------------------------------------------
# host wrapper: the ConflictSet a Resolver instantiates
# ---------------------------------------------------------------------------

def _named(fn, name: str):
    """`fn` under a name of its own: jax.jit calls the program
    `jit_<__name__>`, and a functools.partial has none (`jit__unknown` in a
    profile)."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


def _donate_state_argnums() -> tuple:
    """Donate the state operand (bkeys + table dominate HBM) on accelerator
    backends: the update is written in place of the old state instead of
    alongside it, halving the step's state traffic and footprint. CPU's
    runtime can't alias these buffers and would warn on every program, so
    donation is gated to real accelerators."""
    return (0,) if jax.default_backend() in ("tpu", "gpu") else ()


@functools.lru_cache(maxsize=32)
def _compiled_step(shapes: ConflictShapes, max_write_life: int,
                   intra_rounds: int = 0):
    """One compiled program per (shapes, window, sandwich rounds) — shared
    across instances."""
    return jax.jit(_named(functools.partial(
        conflict_step, shapes=shapes, max_write_life=max_write_life,
        intra_rounds=intra_rounds), "conflict_step"),
        donate_argnums=_donate_state_argnums())


@functools.lru_cache(maxsize=1)
def _compiled_rebase():
    """Compiled rebase_state with the state operand donated: the rebase
    overwrites the engine's only reference to the old state, so eager
    op-by-op dispatch (jnp.maximum + _build_table per call, old buffers
    alive until the host reassignment lands) doubled state traffic for
    nothing. One program per process — delta is a traced scalar."""
    return jax.jit(rebase_state, donate_argnums=_donate_state_argnums())


def _resolve_shapes(capacity=None, txns=None, reads_per_txn=None,
                    writes_per_txn=None, key_bytes=None) -> ConflictShapes:
    k = KNOBS
    t = txns or k.CONFLICT_BATCH_TXNS
    return ConflictShapes(
        capacity=capacity or k.CONFLICT_STATE_CAPACITY,
        txns=t,
        reads=t * (reads_per_txn or k.CONFLICT_BATCH_READS_PER_TXN),
        writes=t * (writes_per_txn or k.CONFLICT_BATCH_WRITES_PER_TXN),
        key_bytes=key_bytes or keylib.KEY_BYTES,
    )


class BatchEncoder:
    """Host-side batch encoding/chunking, shared by the single-device and
    mesh-sharded engines (and the driver entry points)."""

    def __init__(self, shapes: ConflictShapes, base_version: int = 0):
        self.shapes = shapes
        self.L = shapes.limbs
        self.base_version = base_version
        self._rings: dict = {}
        self._last_slot: dict | None = None

    def _clamp_off(self, version: int) -> int:
        off = version - self.base_version
        return int(max(min(off, (1 << 31) - 1), _NEG_INT))

    def _buffers(self, sh: ConflictShapes) -> dict:
        """Reusable encode buffers (a small ring per shape bucket): batch
        N+1 encodes into a slot whose previous dispatch is provably consumed
        (its readback marker is_ready), so the encode output lands straight
        in long-lived host buffers instead of fresh allocations every batch
        — the host side of the dispatch/readback double-buffering. Slots are
        created on demand up to ENCODE_RING; if every slot is still in
        flight the encode falls back to a fresh allocation (never blocks,
        never aliases an in-flight transfer)."""
        T = sh.txns
        ring = self._rings.setdefault((sh.reads, sh.writes), [])
        slot = None
        for s in ring:
            m = s.get("marker")
            if m is None or not hasattr(m, "is_ready") or m.is_ready():
                slot = s
                break
        if slot is None and len(ring) < ENCODE_RING:
            slot = {}
            ring.append(slot)
        if slot is None:
            slot = {}
        if "rb" not in slot:
            slot["rb"] = np.empty((self.L, sh.reads), np.uint32)
            slot["re"] = np.empty((self.L, sh.reads), np.uint32)
            slot["wb"] = np.empty((self.L, sh.writes), np.uint32)
            slot["we"] = np.empty((self.L, sh.writes), np.uint32)
            slot["snap"] = np.empty(T, np.int32)
            slot["valid"] = np.empty(T, bool)
            slot["rtxn"] = np.empty(sh.reads, np.int32)
            slot["wtxn"] = np.empty(sh.writes, np.int32)
        for f in ("rb", "re", "wb", "we"):
            slot[f].fill(0xFFFFFFFF)
        slot["snap"].fill(0)
        slot["valid"].fill(False)
        slot["rtxn"].fill(T)
        slot["wtxn"].fill(T)
        slot["marker"] = None
        self._last_slot = slot
        return slot

    def mark_in_flight(self, marker):
        """Attach the dispatch's readback array to the most recent encode's
        buffer slot: once it is_ready() the step has consumed its inputs and
        the slot becomes reusable."""
        if self._last_slot is not None:
            self._last_slot["marker"] = marker
            self._last_slot = None

    def bucket_shapes(self, nr: int, nw: int) -> ConflictShapes:
        """Smallest shape bucket covering a chunk with nr reads / nw writes.

        Serving batches are usually far smaller than the configured maximum
        (and often one-sided: write-only batches carry zero read ranges), so
        padding every dispatch to the full shape wastes transfer bytes and
        device sort rows. Two buckets per axis (full/16 and full) bound the
        compiled-program count at 4 — the TPU-serving bucketed-padding
        pattern; warmup() pre-compiles all of them."""
        import dataclasses
        sh = self.shapes

        def pick(n, full):
            small = max(full // 16, 8)
            return small if n <= small else full
        r, w = pick(nr, sh.reads), pick(nw, sh.writes)
        if (r, w) == (sh.reads, sh.writes):
            return sh
        return dataclasses.replace(sh, reads=r, writes=w)

    def encode_batch(self, txns: list[TxnConflictInfo], commit_version: int,
                     skip: list[bool] | None = None,
                     shapes: ConflictShapes | None = None):
        """Build one device batch. Key encoding is bulk (C extension when
        available — feeding the device is a host hot path, the analogue of
        the reference's C++ key juggling in SkipList.cpp addTransaction)."""
        sh = shapes or self.shapes
        T = sh.txns
        assert len(txns) <= T
        from foundationdb_tpu import native
        if native.available() and hasattr(native.mod,
                                          "encode_conflict_ranges"):
            return self._encode_batch_c(txns, commit_version, skip, sh)
        rkeys_b: list[bytes] = []
        rkeys_e: list[bytes] = []
        wkeys_b: list[bytes] = []
        wkeys_e: list[bytes] = []
        rt: list[int] = []
        wt: list[int] = []
        buf = self._buffers(sh)
        snap, valid = buf["snap"], buf["valid"]
        for t, txn in enumerate(txns):
            if skip is not None and skip[t]:
                continue  # host already decided TOO_OLD; not in this batch
            valid[t] = True
            snap[t] = self._clamp_off(txn.read_snapshot)
            # oversized txns were rejected by split_for_capacity (the gate on
            # the detect path — raising there happens before any chunk of the
            # logical batch touches device state)
            for b, e in txn.read_ranges:
                rkeys_b.append(b)
                rkeys_e.append(e)
                rt.append(t)
            for b, e in txn.write_ranges:
                wkeys_b.append(b)
                wkeys_e.append(e)
                wt.append(t)

        rb, re, wb, we = buf["rb"], buf["re"], buf["wb"], buf["we"]
        # Leaves stay HOST numpy (long-lived ring buffers, see _buffers):
        # the jitted step's implicit argument transfer is asynchronous and
        # batched (one enqueue), while an explicit device_put per leaf
        # costs a synchronous handshake each.
        _bulk_encode(rkeys_b, rb, round_up=False)
        _bulk_encode(rkeys_e, re, round_up=True)
        _bulk_encode(wkeys_b, wb, round_up=False)
        _bulk_encode(wkeys_e, we, round_up=True)
        rtxn, wtxn = buf["rtxn"], buf["wtxn"]
        rtxn[: len(rt)] = rt
        wtxn[: len(wt)] = wt
        return {
            "rb": rb, "re": re, "rtxn": rtxn,
            "wb": wb, "we": we, "wtxn": wtxn,
            "snapshot": snap, "txn_valid": valid,
            "commit_version": np.int32(self._clamp_off(commit_version)),
            "advance_floor": np.bool_(True),
        }

    def _encode_batch_c(self, txns: list[TxnConflictInfo],
                        commit_version: int, skip: list[bool] | None,
                        sh: ConflictShapes):
        """Pooled-layout encode with the C flattener: one native pass writes
        keys (limb-encoded) + range→txn maps straight into the buffers,
        replacing the per-range Python loop (the host hot path when the
        device engine serves live commit batches)."""
        from foundationdb_tpu import native
        T = sh.txns
        buf = self._buffers(sh)
        rb, re, wb, we = buf["rb"], buf["re"], buf["wb"], buf["we"]
        rtxn, wtxn = buf["rtxn"], buf["wtxn"]
        snap, valid = buf["snap"], buf["valid"]
        native.mod.encode_conflict_ranges(
            txns, skip, rb, re, wb, we, rtxn, wtxn, (self.L - 1) * 4,
            snap, valid, self.base_version)
        return {
            "rb": rb, "re": re, "rtxn": rtxn,
            "wb": wb, "we": we, "wtxn": wtxn,
            "snapshot": snap, "txn_valid": valid,
            "commit_version": np.int32(self._clamp_off(commit_version)),
            "advance_floor": np.bool_(True),
        }

    def split_for_capacity(self, txns):
        sh = self.shapes
        subs, cur, nr, nw = [], [], 0, 0
        for txn in txns:
            tr, tw = len(txn.read_ranges), len(txn.write_ranges)
            if tr > sh.reads or tw > sh.writes:
                raise FDBError("transaction_too_large",
                               f"{tr} reads / {tw} writes exceed batch shape")
            if cur and (nr + tr > sh.reads or nw + tw > sh.writes or len(cur) >= sh.txns):
                subs.append(cur)
                cur, nr, nw = [], 0, 0
            cur.append(txn)
            nr += tr
            nw += tw
        subs.append(cur)
        return subs


def detect_async_impl(engine, txns: list[TxnConflictInfo],
                      commit_version: int) -> "DetectHandle":
    """Enqueue a whole logical batch on device and return a handle; no
    host↔device synchronization happens until handle.result().

    Shared by DeviceConflictSet and ShardedDeviceConflictSet (`engine` needs:
    encoder, _step, _state, oldest_version, _maybe_rebase). This is the
    proxy's pipelining pattern (MasterProxyServer.actor.cpp:364-366,426-428):
    batch N+1's transfer/compute overlaps batch N's result readback.
    """
    engine._maybe_rebase(commit_version)
    enc = engine.encoder
    subs = enc.split_for_capacity(txns)
    # The too-old decision is taken here with exact int64 versions (device
    # offsets saturate across extreme rebases); flagged txns are excluded
    # from the device batch entirely.
    pre_batch_oldest = engine.oldest_version
    base = enc.base_version
    chunks = []
    # the resolver's ident for this batch, and its loop's clock (virtual
    # under the simulator); an engine driven directly stamps time.monotonic
    vid = f"v{commit_version}"
    clock = getattr(engine, "trace_clock", None) or time.monotonic
    for i, sub in enumerate(subs):
        # TOO_OLD when below the MVCC floor, AND when the snapshot's device
        # offset would saturate at the NEG sentinel (a >2^30-stale snapshot
        # after a rebase): a saturated snapshot compares equal to "no
        # version" and would silently MISS conflicts — rejecting it is the
        # conservative direction (the reference also throws too_old for
        # anything beyond its window, SkipList.cpp:985 semantics)
        host_too_old = [bool(t.read_ranges)
                        and (t.read_snapshot < pre_batch_oldest
                             or t.read_snapshot - base <= _NEG_INT)
                        for t in sub]
        nr = sum(len(t.read_ranges) for t, old in zip(sub, host_too_old)
                 if not old)
        nw = sum(len(t.write_ranges) for t, old in zip(sub, host_too_old)
                 if not old)
        shapes, step = engine.plan_chunk(nr, nw)
        with g_trace_batch.section("CommitSpan", vid, "Resolver.Encode",
                                   now=clock):
            batch = enc.encode_batch(sub, commit_version, skip=host_too_old,
                                     shapes=shapes)
            # the MVCC floor advances once per logical batch (last chunk),
            # so every chunk's too-old check uses the pre-batch floor
            batch["advance_floor"] = np.bool_(i == len(subs) - 1)
        with g_trace_batch.section("CommitSpan", vid, "Resolver.Enqueue",
                                   now=clock):
            _kernel_dispatches.increment()
            # the encoded batch crosses to the device inside the jit call
            jaxenv.count_device_put(batch)
            new_state, statuses, info = step(engine._state, batch)
            engine._state = new_state
            # statuses + intra-eligibility + overflow + convergence + the
            # state's fill and churn fused into ONE fixed-shape device array
            # (enqueue-only): every chunk is read back as a single transfer
            combined = _combine_status(statuses, info)
            enc.mark_in_flight(combined)
            # double-buffering: the D2H copy starts NOW, overlapped with the
            # NEXT chunk's/batch's encode + dispatch, so a later drain (or
            # result()) finds the bytes already on the host instead of
            # starting the transfer under a sync (a host-evaluated array has
            # no such method).
            if hasattr(combined, "copy_to_host_async"):
                combined.copy_to_host_async()
        chunks.append((sub, host_too_old, combined))
    # the kernel's floor advance is replicated host-side exactly
    # (floor = commit_version - window on the last chunk, monotonic max)
    engine.oldest_version = max(
        engine.oldest_version,
        commit_version - KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS)
    return DetectHandle(chunks, vid, clock)


class DeviceConflictSet:
    """Drop-in conflict set backed by the jitted device step.

    Mirrors the seam in fdbserver/ConflictSet.h:27-44: construct, feed batches
    of TxnConflictInfo, get {CONFLICT, TOO_OLD, COMMITTED} per transaction.
    Arbitrary batch sizes are handled by chunking to the static shape
    (chunk order preserves batch order, so intra-batch "earlier txns win"
    semantics are exact: later chunks see earlier chunks' merged writes).
    """

    def __init__(self, capacity: int | None = None, txns: int | None = None,
                 reads_per_txn: int | None = None, writes_per_txn: int | None = None,
                 oldest_version: int = 0, key_bytes: int | None = None):
        install_profiler_annotator()
        self.shapes = _resolve_shapes(capacity, txns, reads_per_txn,
                                      writes_per_txn, key_bytes)
        self.encoder = BatchEncoder(self.shapes, base_version=oldest_version)
        self.oldest_version = oldest_version
        self._state = init_state(self.shapes, oldest=0)
        self._intra = int(KNOBS.CONFLICT_INTRA_ROUNDS)
        self._step = _compiled_step(self.shapes,
                                    KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS,
                                    self._intra)

    @property
    def base_version(self) -> int:
        return self.encoder.base_version

    def _maybe_rebase(self, commit_version: int):
        # Shift in <= 2^30 steps so each delta fits int32; values saturate at
        # NEG, so repeated shifts are exact for any version gap.
        while commit_version - self.encoder.base_version > _REBASE_THRESHOLD:
            delta = min(commit_version - self.encoder.base_version - (1 << 24),
                        1 << 30)
            self._state = _compiled_rebase()(self._state, np.int32(delta))
            self.encoder.base_version += delta

    # -- ConflictBatch interface --
    def detect(self, txns: list[TxnConflictInfo], commit_version: int) -> list[int]:
        return self.detect_async(txns, commit_version).result()

    def detect_async(self, txns: list[TxnConflictInfo],
                     commit_version: int) -> "DetectHandle":
        return detect_async_impl(self, txns, commit_version)

    def plan_chunk(self, nr: int, nw: int):
        """(shapes, compiled step) for a chunk: bucketed padding keeps the
        transfer bytes and the device sort sized to the chunk, not to the
        configured maximum (see BatchEncoder.bucket_shapes)."""
        shapes = self.encoder.bucket_shapes(nr, nw)
        return shapes, _compiled_step(
            shapes, KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS, self._intra)

    def _bucket_programs(self):
        """(shapes, compiled step, an empty batch) of every serving bucket."""
        sh = self.shapes
        combos = {(r, w)
                  for r in (0, sh.reads) for w in (0, sh.writes)}
        for nr, nw in sorted(combos):
            shapes, step = self.plan_chunk(nr, nw)
            batch = self.encoder.encode_batch(
                [], self.encoder.base_version + 1, shapes=shapes)
            yield shapes, step, batch

    def warmup(self):
        """Compile every serving bucket now (boot-time cost, served-path
        savings; the persistent compile cache makes it once per machine)."""
        for _shapes, step, batch in self._bucket_programs():
            new_state, statuses, _info = step(self._state, batch)
            self._state = new_state
            statuses.block_until_ready()

    def write_scope_maps(self, directory: str) -> None:
        """One `scopes.conflict_step.<reads>x<writes>.json` per bucket
        program: which scope each instruction of the compiled module runs
        under. The profiler's trace of this chip names an operation by its
        instruction and carries no op_name, so a reader of a profile needs
        this beside it. Compiles each program once more (from the persistent
        cache where there is one): for a traced run's warm-up only."""
        for shapes, step, batch in self._bucket_programs():
            write_scope_map(
                directory, shapes,
                step.lower(self._state, batch).compile().as_text())

    def clear(self, oldest_version: int = 0):
        """clearConflictSet (SkipList.cpp:957): state is soft/reconstructable."""
        self.encoder.base_version = oldest_version
        self.oldest_version = oldest_version
        self._state = init_state(self.shapes, oldest=0)


# the step's scalars that ride each chunk's verdicts, in readback order
_STATUS_SCALARS = ("overflow", "converged", "boundaries", "evicted")


@functools.cache
def _combine_fn():
    # one program per process: statuses/eligible are always (shapes.txns,),
    # the rest scalars — the fixed output layout
    # [statuses | eligible | *_STATUS_SCALARS] makes every chunk readback a
    # single transfer
    def combine_status(s, g, *scalars):
        return jnp.concatenate(
            [s.astype(jnp.int32), g.astype(jnp.int32)]
            + [jnp.asarray(x, jnp.int32)[None] for x in scalars])
    return jax.jit(combine_status)


def _combine_status(statuses, info):
    return _combine_fn()(statuses, info["eligible"],
                         *(info[name] for name in _STATUS_SCALARS))


def _status_to_host(combined) -> np.ndarray:
    """Materialise a chunk's combined status array, counted once: this is
    where the served path's bytes come back from the device."""
    if isinstance(combined, np.ndarray):
        return combined  # a drain already brought it over
    jaxenv.count_device_get(combined)
    return np.asarray(combined)


def drain_handles(handles: list["DetectHandle"]) -> None:
    """Materialize many DetectHandles with overlapped device→host copies.

    Each pending chunk's combined status array gets an ASYNC host copy
    enqueued first; the materializing np.asarray then finds the data already
    in flight, so N batches' readbacks cost ~one device round trip total
    instead of N. result() on each
    handle afterwards touches no device state: round-trip latency is paid
    once per DRAIN, so resolver throughput is set by dispatch rate, not
    round-trip time.
    """
    pend = [h for h in handles if h._result is None and h._chunks]
    arrs = [c[2] for h in pend for c in h._chunks]
    for a in arrs:
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()
    for h in pend:
        h._chunks = [(sub, too_old, _status_to_host(a))
                     for sub, too_old, a in h._chunks]


def drain_and_collect(
        handles: list["DetectHandle"], timing: dict | None = None,
) -> list[tuple[list[int] | None, "FDBError | None"]]:
    """drain_handles + result() for every handle, entirely off-loop.

    One (statuses, error) pair per handle, in order. This exists so a
    coroutine can offload the WHOLE materialization in a single
    loop.run_blocking(...) call: result() can fall back to the exact host
    intra-batch pass (_exact_intra_host) on an unconverged chunk, which is
    milliseconds of host compute the event-loop thread should never eat.
    Errors are returned, not raised — a capacity overflow on one handle
    must not strand the remaining handles' results.

    When `timing` is given, the device-sync ("drain_seconds") and host-
    materialization ("collect_seconds") halves are recorded separately so
    the caller can attribute them to distinct spans (the sharded path bills
    the verdict unpack as Resolver.ShardCombine)."""
    # one device sync serves the whole group: its section carries the first
    # batch's ident, and each batch's unpack its own
    first = handles[0] if handles else DetectHandle([])
    t0 = time.perf_counter()
    with g_trace_batch.section("CommitSpan", first.ident,
                               "Resolver.Readback", now=first.clock):
        drain_handles(handles)
    t1 = time.perf_counter()
    out: list[tuple[list[int] | None, FDBError | None]] = []
    for h in handles:
        with g_trace_batch.section("CommitSpan", h.ident,
                                   "Resolver.Collect", now=h.clock):
            try:
                out.append((h.result(), None))
            except FDBError as e:
                out.append((None, e))
    t2 = time.perf_counter()
    if timing is not None:
        timing["drain_seconds"] = t1 - t0
        timing["collect_seconds"] = t2 - t1
    _readback_waits.increment()
    _readback_wait_seconds.increment(t2 - t0)
    return out


def _exact_intra_host(sub, host_too_old, eligible):
    """Exact sequential intra-batch resolution for an unconverged chunk.

    The device's sandwich bound ran out before the chunk's dependency chains
    pinched (possible only for chains deeper than 2*rounds). Its too-old and
    history decisions are exact regardless (`eligible` = survived both), so
    the remaining greedy "earlier txns win" pass runs here against the
    chunk's original byte ranges — the same loop as the oracle's step 3.
    The device merged the sandwich UPPER bound into its state (a superset of
    the writes committed here), which can only create false conflicts for
    later batches, never false commits."""
    from foundationdb_tpu.ops.conflict_oracle import _RangeSet
    statuses = []
    published = _RangeSet()
    for t, txn in enumerate(sub):
        if host_too_old[t]:
            statuses.append(TOO_OLD)
            continue
        if not eligible[t]:
            statuses.append(CONFLICT)
            continue
        if any(published.overlaps(b, e) for b, e in txn.read_ranges):
            statuses.append(CONFLICT)
            continue
        for b, e in txn.write_ranges:
            published.add(b, e)
        statuses.append(COMMITTED)
    return statuses


class DetectHandle:
    """Deferred result of detect_async: statuses fetched on first result().

    Each chunk is (sub_txns, host_too_old, combined) where combined is the
    device readback [statuses(T) | eligible(T) | overflow | converged |
    boundaries | evicted]. After result(), `steps` holds one (boundaries the
    state held after the step, rows its window dropped) per chunk."""

    def __init__(self, chunks, ident: str = "", clock=time.monotonic):
        self._chunks = chunks
        self._result: list[int] | None = None
        self.steps: list[tuple[int, int]] = []
        self.ident, self.clock = ident, clock  # for drain_and_collect's sections

    def result(self) -> list[int]:
        if self._result is None:
            out: list[int] = []
            for sub, host_too_old, combined in self._chunks:
                arr = _status_to_host(combined)
                n = len(sub)
                tc = (len(arr) - len(_STATUS_SCALARS)) // 2
                overflow, converged, boundaries, evicted = arr[2 * tc:]
                if overflow:
                    # Overflow: the truncated state dropped the highest-key
                    # history segments and could cause false commits —
                    # fatal; the owner reconstructs (clearConflictSet
                    # semantics, SkipList.cpp:957: conflict state is soft).
                    raise FDBError(
                        "internal_error",
                        "conflict state capacity exceeded; raise CONFLICT_STATE_CAPACITY")
                self.steps.append((int(boundaries), int(evicted)))
                if converged:
                    statuses = arr[:n]
                else:
                    _host_exact_chunks.increment()
                    statuses = _exact_intra_host(sub, host_too_old,
                                                 arr[tc:tc + n])
                out.extend(TOO_OLD if old else int(s)
                           for s, old in zip(statuses, host_too_old))
            self._result = out
            self._chunks = None
        return self._result
