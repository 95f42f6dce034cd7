"""The cells of PR 34, `fdb-write-1m` and `fdb-mixed-90-10`, rehearsed on the
CPU from a tiny benchmark file of their own (`data/BENCHMARK.tiny-pr34.json`),
as test_four_resolvers.py does for PR 28's: the one-device engine on the
host's XLA (not the oracle), a key space larger than a window's writes and a
window short enough that a run of two seconds inserts and evicts boundaries
every step, so that the two metrics this PR adds read numbers off the
counters this PR adds. And the step's bytes at the new configuration's
capacity, by hand.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_rehearsal import DRIVE

TINY = os.path.join(BENCH, "tests", "data", "BENCHMARK.tiny-pr34.json")


def run_cell(**kw) -> tuple[dict, list[dict]]:
    """(the result, the earlier lines) of one run in a process of its own."""
    p = subprocess.run(
        [sys.executable, "-c", DRIVE.format(bench=BENCH, root=ROOT),
         json.dumps(dict(kw, bench_file=TINY))],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.splitlines()]
    return lines[-1], lines[:-1]


def test_step_bytes_at_two_to_the_nineteenth():
    """`kernel.conflict_step_roofline` in `fdb-write-1m` reads the capacity
    from the configuration's knobs: the state's arrays at 2^19 rows (7 limbs
    of keys, the values, a table of 20 levels), read and written once."""
    import kernel_cost
    with open(os.path.join(BENCH, "configs", "fdb-bench-1chip-1m.json")) as f:
        shapes = kernel_cost.conflict_shapes(json.load(f))
    assert shapes == {"capacity": 524288, "txns": 256, "reads": 2560,
                      "writes": 2560, "key_bytes": 24}
    state = 4 * 524288 * (7 + 1 + 20) + 4 + 4 + 1
    batch = (4 * 7 * 2 * 5120) + 4 * 5120 + (4 * 256 + 256) + 5
    by_hand = 2 * state + batch + 4 * 256
    assert by_hand == 117_750_039
    assert kernel_cost.conflict_step_bytes(**shapes) == by_hand


def test_the_configuration_is_fdb_bench_1chip_at_another_scale():
    """Everything but the record count and the capacity is `fdb-write`'s
    deployment: same widths, cluster, batch shapes and guarantees."""
    def load(name):
        with open(os.path.join(BENCH, "configs", name)) as f:
            return json.load(f)
    small, large = load("fdb-bench-1chip.json"), load("fdb-bench-1chip-1m.json")
    assert large["data"].pop("records") == 1_000_000
    assert small["data"].pop("records") == 100_000
    assert large["knobs"].pop("CONFLICT_STATE_CAPACITY") == 1 << 19
    assert small["knobs"].pop("CONFLICT_STATE_CAPACITY") == 1 << 18
    for part in ("chips", "cluster", "knobs", "data", "guarantees"):
        assert large[part] == small[part], part
    assert large["architecture"] is None
    assert set(large["reduced"]) == {"records", "replicas", "processes"}


def test_both_cells_are_entries_of_the_benchmark_over_files_that_are_there():
    """The cells as BENCHMARK.json has them: one chip each, the mix files
    the benchmark already had, the two metrics listed for every one-chip
    cell and read by the reader that is there."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells["fdb-write-1m"]["config"],
            cells["fdb-write-1m"]["traffic"]) == ("fdb-bench-1chip-1m",
                                                  "write10-192")
    assert (cells["fdb-mixed-90-10"]["config"],
            cells["fdb-mixed-90-10"]["traffic"]) == ("fdb-bench-1chip",
                                                     "mixed-90-10")
    for name in ("fdb-write-1m", "fdb-mixed-90-10"):
        assert cells[name]["chips"] == 1 and len(cells[name]["why"]) <= 200
    one_chip = [w["name"] for w in bench["workloads"] if w["chips"] == 1]
    for name in ("resolver.state_fill", "resolver.state_evictions_per_step"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == one_chip
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == "counter_ratio"


@pytest.mark.parametrize("workload,trace", [
    ("fdb-write-1m", False), ("fdb-write-1m", True),
    ("fdb-mixed-90-10", False), ("fdb-mixed-90-10", True)])
def test_a_new_cell_fills_its_state_by_the_rate_on_the_cpu(workload, trace):
    result, lines = run_cell(workload=workload, seed=3_400_000_023,
                             seconds=2.0, trace=trace,
                             env_extra={"JAX_PLATFORMS": "cpu"})
    boot = next(line for line in lines if line["line"] == "boot")
    assert boot["Backend"] == "cpu" and boot["DeviceCount"] == 1
    resolver = next(line for line in lines if line["line"] == "resolver")
    assert resolver["Poisoned"] is False
    assert result["correct"] is True, result["compared"]
    assert all(c["value"] == 0 for c in result["compared"].values())
    assert result["failed"] == 0 and result["attempted"] > 50
    with open(TINY) as f:
        bench = json.load(f)
    kind = "per_layer" if trace else "end_to_end"
    # no chip, no profile: the device's readers find nothing and say nothing
    assert set(result["metrics"]) == {
        m["name"] for m in bench[kind] if m["source"] != "device_trace"
        and workload in m.get("workloads", [workload])}
    if trace:
        m = {k: v["value"] for k, v in result["metrics"].items()}
        # a window's writes (0.2 s of them: the faster the host, the more),
        # not two boundaries for every key of the cell, which reads 98%
        assert 0.0 < m["resolver.state_fill"] < 60.0
        assert m["resolver.state_evictions_per_step"] > 1.0
