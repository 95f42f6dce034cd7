"""Several runs of cells in one call on the chip, with what the bounds are set
from: for each metric of each cell the runs' values, their median and two
spreads, each as a share of the median: `spread`, the distance between the first
and third quartile by `statistics.quantiles(values, n=4)`, and `spread_driver`,
the driver's reading of a set as its refusals state it (PERF_LEDGER.jsonl,
PR 32 `reason`): the range of the values once the run farthest from the
median is left out. `commit_p90_ms`' bound is set from the second (PERF.md §2).

    python benchmark/sets.py --out chiprun_out/<tag> --seconds 20 \
        --runs fdb-write:0:11,12,13 ycsb-f:1:21

Each `--runs` word is `<cell>:<trace 0|1>:<seed>,<seed>,...`; the runs are made
one after another, each a new process of the benchmark's own command. Every
run's stdout and stderr are kept under `--out`. A tool for the sessions that
set or check bounds; the driver does not run it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values: list[float]) -> float | None:
    if len(values) < 2 or statistics.median(values) == 0:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def spread_driver(values: list[float]) -> float | None:
    median = statistics.median(values) if values else 0
    if len(values) < 2 or median == 0:
        return None
    kept = sorted(values)
    if len(kept) > 2:
        kept.remove(max(kept, key=lambda v: abs(v - median)))
    return (kept[-1] - kept[0]) / abs(median)


def keep_rows(run_dir: str, dst: str) -> None:
    import numpy as np
    logs = sorted(glob.glob(os.path.join(run_dir, "worker*.npy")))
    if not logs:
        return
    rows = np.concatenate([np.load(p) for p in logs])
    np.savez_compressed(dst, t0=rows["t0"], t1=rows["t1"],
                        status=rows["status"], writes=rows["writes"],
                        attempts=rows["attempts"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", nargs="+", required=True)
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy a traced run's .xplane.pb under --out")
    ap.add_argument("--keep-rows", action="store_true",
                    help="keep every transaction's times under --out, so that "
                         "another statistic can be tried on the same runs")
    ap.add_argument("--root", default=ROOT,
                    help="the checkout to run (default: this one)")
    args = ap.parse_args()
    with open(os.path.join(args.root, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    os.makedirs(args.out, exist_ok=True)
    table: dict = {}
    bad = 0
    for word in args.runs:
        cell, trace, seeds = word.split(":")
        for seed in seeds.split(","):
            tag = f"{cell}.t{trace}.s{seed}.{int(time.time())}"
            t0 = time.monotonic()
            with open(os.path.join(args.out, tag + ".out"), "wb") as out, \
                    open(os.path.join(args.out, tag + ".err"), "wb") as err:
                rc = subprocess.run(
                    command + ["--workload", cell, "--seed", seed, "--seconds",
                               f"{args.seconds:g}", "--trace", trace],
                    cwd=args.root, stdout=out, stderr=err).returncode
            wall = time.monotonic() - t0
            if args.keep_trace and trace == "1":
                for path in glob.glob(os.path.join(
                        args.root, ".bench_run", cell, "profile", "**",
                        "*.xplane.pb"), recursive=True):
                    shutil.copy(path, os.path.join(args.out,
                                                   tag + ".xplane.pb"))
            if args.keep_rows:
                keep_rows(os.path.join(args.root, ".bench_run", cell),
                          os.path.join(args.out, tag + ".rows.npz"))
            with open(os.path.join(args.out, tag + ".out")) as f:
                lines = f.read().splitlines()
            result = None
            if rc == 0 and lines:
                result = json.loads(lines[-1])
            ok = bool(result and result["correct"])
            bad += not ok
            row = {"seed": seed, "rc": rc, "wall_s": round(wall, 1),
                   "correct": result and result["correct"],
                   "attempted": result and result["attempted"],
                   "failed": result and result["failed"]}
            if result:
                row.update({k: v["value"]
                            for k, v in result["metrics"].items()})
                row["memory_peak_bytes"] = result["device"].get(
                    "memory_peak_bytes")
                for k in ("busy_s", "window_s"):
                    if k in result["device"]:
                        row[k] = result["device"][k]
                if not ok:
                    row["compared"] = result.get("compared")
            print(json.dumps({"run": f"{cell}:{trace}", **row}), flush=True)
            table.setdefault((cell, trace), []).append(row)
    for (cell, trace), rows in table.items():
        if len(rows) < 2:
            continue
        names = [k for k in rows[0] if k not in (
            "seed", "rc", "correct", "compared")]
        for name in names:
            values = [r[name] for r in rows if isinstance(
                r.get(name), (int, float))]
            if not values:
                continue
            sp, spd = spread(values), spread_driver(values)
            later = values[1:] if name == "setup_s" and len(values) > 2 \
                else values
            print(json.dumps({
                "cell": cell, "trace": trace, "metric": name,
                "n": len(values), "median": statistics.median(values),
                "median_after_first": statistics.median(later),
                "min": min(values), "max": max(values),
                "spread": sp if sp is None else round(sp, 5),
                "spread_driver": spd if spd is None else round(spd, 5)}),
                flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
