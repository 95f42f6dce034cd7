"""Ablation profile of the conflict kernel on the real chip.

Times the full conflict_scan and variants with pieces disabled to get a
truthful per-phase cost breakdown (sync = small D2H fetch). Usage:
    python scripts/profile_kernel.py [T] [NBATCH]
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, "/root/repo")
import bench
from foundationdb_tpu.ops import conflict as C
from foundationdb_tpu.utils import jaxenv
from foundationdb_tpu.utils.knobs import KNOBS

T = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
NB = int(sys.argv[2]) if len(sys.argv) > 2 else 50
CAP = 1 << 18
WINDOW = KNOBS.MAX_WRITE_TRANSACTION_LIFE_VERSIONS

bench.TXNS_PER_BATCH = T
shapes = C.ConflictShapes(capacity=CAP, txns=T, reads=T, writes=T, key_bytes=16)


def timed(name, fn, state, stacked, n=3):
    # warmup/compile
    out = fn(state, stacked)
    s = np.asarray(jax.tree_util.tree_leaves(out)[-1])[:1]  # sync
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(state, stacked)
        np.asarray(out[2])  # comm (NB,) small fetch = sync
        ts.append(time.perf_counter() - t0)
    dt = min(ts)
    per_batch = dt / NB * 1e3
    print(f"{name:28s} {dt:7.3f}s  {per_batch:7.2f} ms/batch  "
          f"{T * NB / dt / 1e3:8.0f} ktxn/s")
    return dt


def make_scan(step_kwargs):
    def stepfn(st, batch):
        st2, statuses, info = C.conflict_step(
            st, batch, shapes=shapes,
            max_write_life=WINDOW, **step_kwargs)
        return st2, (statuses.astype(jnp.int8), info["committed"],
                     info["overflow"])

    @jax.jit
    def scan(st, stacked):
        final, (stat, comm, ovf) = lax.scan(stepfn, st, stacked)
        return final, stat, comm, ovf
    return scan


def main():
    warm_np = bench._encode_batches(8, seed=1, version0=WINDOW)
    main_np = bench._encode_batches(NB, seed=2, version0=WINDOW + 8 * bench.VERSION_STEP)
    warm = jaxenv.device_put(warm_np)
    stacked = jaxenv.device_put(main_np)
    state0 = C.init_state(shapes, oldest=0)

    scan_full = make_scan({})
    # fill history so the state has realistic boundary count
    state, _, _, ovf = scan_full(state0, warm)
    print("warm overflow:", bool(np.asarray(ovf).any()),
          " nb:", int(np.asarray(state["nb"])))

    timed("full", scan_full, state, stacked)

    for abl in ["no_merge", "no_intra", "no_hist", "no_table",
                "only_merge", "only_hist"]:
        timed(abl, make_scan({"ablate": abl}), state, stacked)


if __name__ == "__main__":
    main()
