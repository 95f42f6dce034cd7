"""The rehearsal: the same run.py, cluster.py, client workers, readers and
reference as on the chip, at a tiny shape with the oracle backend on the CPU.
It proves paths, arguments and the form of the result before chip time is
spent; it measures nothing. The same drive, with the timed path broken
underneath (tests/faults), has to come out `correct: false`."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

TINY = os.path.join(BENCH, "tests", "data", "BENCHMARK.tiny.json")
FAULTS = os.path.join(BENCH, "tests", "faults")
# the oracle backend has no device: these read 0 or nothing there
DEVICE_ONLY = {"resolver.readback_wait_ms"}
DRIVE = """
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
kw = json.loads(sys.argv[1])
print(json.dumps(run.run_cell({root!r}, require_chip=False, **kw)))
"""


def run_cell(**kw) -> dict:
    """One run in a process of its own, as run.py's is: a traced run reads
    its profile with JAX once the servers are gone, and a process that has
    touched JAX may start no further cluster."""
    p = subprocess.run(
        [sys.executable, "-c", DRIVE.format(bench=BENCH, root=ROOT),
         json.dumps(dict(kw, bench_file=TINY))],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("workload,trace", [
    ("fdb-write", False), ("ycsb-f", True), ("ycsb-b", False)])
def test_a_cell_runs_end_to_end_on_the_cpu(workload, trace):
    result = run_cell(workload=workload, seed=2_200_000_011, seconds=2.0,
                      trace=trace)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 50
    with open(TINY) as f:
        bench = json.load(f)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind]
            if workload in m.get("workloads", [workload])}
    # no chip, no profile: the device's readers find nothing and say nothing
    want -= {m["name"] for m in bench["per_layer"]
             if m["source"] == "device_trace"}
    assert set(result["metrics"]) == want
    for name, m in result["metrics"].items():
        assert m["unit"] and (m["value"] > 0 or name in DEVICE_ONLY), name
    json.dumps(result)


@pytest.mark.parametrize("workload,core,storage,fault,number", [
    ("ycsb-f", "core_commits_everything.py", None, None,
     "conflict_violations"),
    ("fdb-write", None, "storage_faulty.py", "drop_write",
     "readback_mismatches"),
    ("ycsb-b", None, "storage_faulty.py", "alter_read", "read_mismatches"),
])
def test_a_run_over_a_broken_path_is_not_correct(workload, core, storage,
                                                 fault, number):
    result = run_cell(
        workload=workload, seed=2_200_000_017, seconds=2.0, trace=False,
        core_entry=core and [sys.executable, os.path.join(FAULTS, core)],
        storage_entry=storage and [sys.executable,
                                   os.path.join(FAULTS, storage)],
        env_extra={"BENCH_TEST_FAULT": fault} if fault else None)
    assert result["correct"] is False
    got = result["compared"][number]
    assert got["value"] > got["limit"] == 0, result["compared"]


def test_cpu_is_refused_without_measuring():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "fdb-write", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr
