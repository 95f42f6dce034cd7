"""The control of `correct`: the reference put in the program's place, with
one guarantee the configuration states broken, has to come out NOT correct.

    python benchmark/control.py --workload <cell> --seed <n> [--txns N]
        [--bench-file <a BENCHMARK.json with a cell that the checkout's lacks>]

Drives the cell's own traffic (its mix, its data, as many actors) through
reference.RefCluster by the same actor loop as the program's clients, then
judges the history by the same `check_history`. Three systems are run: the
sound reference (has to be correct), and the reference with `isolation` and
with `durability` broken (each has to fail a number of the cell that
exercises that guarantee). Needs no chip and starts no process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def run_control(config: dict, mix: dict, seed: int, txns: int,
                broken: str | None) -> tuple[bool, dict, dict]:
    import numpy as np

    from actor import LOG_DTYPE, run_actor
    from reference import (LIMITS, RefCluster, check_history, judge,
                           run_by_turns)
    from traffic import Data, Traffic
    data = Data(config["data"], seed)
    traffic = Traffic(mix, data)
    initial = data.initial_values()
    pool = traffic.make_pool(seed)
    cluster = RefCluster(data.keys, initial, broken=broken)
    n_workers = int(mix["clients"]["processes"])
    n_actors = int(mix["clients"]["actors_per_process"])
    rows = {w: [] for w in range(n_workers)}
    done = [0]

    def keep_going() -> bool:
        done[0] += 1
        return done[0] <= txns

    actors = [run_actor(cluster, traffic, data.keys, pool,
                        traffic.actor_rng(seed, w, a), a, rows[w], keep_going)
              for w in range(n_workers) for a in range(n_actors)]
    run_by_turns(cluster, actors)
    logs = {w: np.array(r, dtype=LOG_DTYPE) for w, r in rows.items()}
    final = cluster.final()
    readback = {data.index[k]: v for k, v in final.items()}
    numbers, notes = check_history(traffic, seed, pool, initial, logs, readback)
    correct, compared = judge(numbers, LIMITS)
    return correct, compared, notes


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--txns", type=int, default=60000)
    ap.add_argument("--bench-file")
    args = ap.parse_args()
    from run import load_cell
    _bench, _cell, _entry, config, mix = load_cell(ROOT, args.workload,
                                                   args.bench_file)
    ok = True
    for broken in (None, "isolation", "durability"):
        correct, compared, notes = run_control(config, mix, args.seed,
                                               args.txns, broken)
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "broken": broken, "correct": correct,
                          "compared": compared, "notes": notes}), flush=True)
        if broken is None:
            ok &= correct
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
