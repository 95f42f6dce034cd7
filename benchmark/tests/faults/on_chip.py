"""The planted faults at a cell's own size, on the chip: a whole run of run.py
over a server broken underneath has to print `correct: false`.

    python3 benchmark/tests/faults/on_chip.py --workload ycsb-f \
        --fault core_commits_everything --seeds 11,12,13 --seconds 10

`--fault` is `core_commits_everything`, `drop_write` or `alter_read`. Each
seed is a process of its own, as a run of run.py is. Exit 0 when every run
came out not correct.
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
ROOT = os.path.dirname(BENCH)
DRIVE = """
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
print(json.dumps(run.run_cell({root!r}, **json.loads(sys.argv[1]))))
"""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=(
        "core_commits_everything", "drop_write", "alter_read"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    kw = {"workload": args.workload, "seconds": args.seconds, "trace": False}
    if args.fault == "core_commits_everything":
        kw["core_entry"] = [sys.executable, os.path.join(
            HERE, "core_commits_everything.py")]
    else:
        kw["storage_entry"] = [sys.executable,
                               os.path.join(HERE, "storage_faulty.py")]
        kw["env_extra"] = {"BENCH_TEST_FAULT": args.fault}
    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        p = subprocess.run(
            [sys.executable, "-c", DRIVE.format(bench=BENCH, root=ROOT),
             json.dumps(dict(kw, seed=seed))],
            capture_output=True, text=True, cwd=ROOT)
        if p.returncode != 0:
            print(json.dumps({"seed": seed, "rc": p.returncode,
                              "stderr": p.stderr[-1500:]}), flush=True)
            continue
        result = json.loads(p.stdout.splitlines()[-1])
        caught += result["correct"] is False
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "compared": {k: v["value"] for k, v in
                                       result["compared"].items()}}),
              flush=True)
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
