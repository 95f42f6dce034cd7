"""The cell of PR 28, `fdb-write-4res`, rehearsed on the CPU from a tiny
benchmark file of its own (`data/BENCHMARK.tiny-pr28.json`), as
test_rehearsal.py does for the first three, but with the key-partitioned
engine on four forced host devices, so that the rehearsal walks the
partition (cold cuts, the load in ascending order, moves, the counters and
spans the new metrics read) and not the oracle. And the step's bytes at the
four-chip configuration's shapes, by hand.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_rehearsal import DRIVE

TINY = os.path.join(BENCH, "tests", "data", "BENCHMARK.tiny-pr28.json")
FOUR_HOST_DEVICES = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
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


def wanted(workload: str, kind: str) -> set:
    with open(TINY) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench[kind]
             if workload in m.get("workloads", [workload])}
    # no chip, no profile: the device's readers find nothing and say nothing
    return names - {m["name"] for m in bench["per_layer"]
                    if m["source"] == "device_trace"}


def test_step_bytes_at_a_shard_of_two_to_the_sixteenth():
    """`kernel.conflict_step_roofline` in `fdb-write-4res` is one chip's
    bytes over one chip's time: the configuration's capacity is a shard's,
    and the engine always runs the full bucket (2,560 reads, 2,560 writes)."""
    import kernel_cost
    with open(os.path.join(BENCH, "configs", "fdb-bench-4chip.json")) as f:
        shapes = kernel_cost.conflict_shapes(json.load(f))
    assert shapes == {"capacity": 65536, "txns": 256, "reads": 2560,
                      "writes": 2560, "key_bytes": 24}
    state = 4 * 65536 * (7 + 1 + 17) + 4 + 4 + 1   # 7 limbs, bval, 17 levels
    batch = (4 * 7 * 2 * 5120) + 4 * 5120 + (4 * 256 + 256) + 5
    by_hand = 2 * state + batch + 4 * 256
    assert by_hand == 13_416_727
    assert kernel_cost.conflict_step_bytes(**shapes) == by_hand


def test_step_scopes_reads_clip_and_combine_and_nothing_where_none_ran(
        tmp_path, monkeypatch):
    """The reader of `kernel.step_clip_ms` / `kernel.step_combine_ms`: ms an
    execution under the SPMD step's own scopes; None for a program that has
    none of them (the one-chip step, a parent without the scope map), never
    an error."""
    from readers import step_phases, step_scopes
    ns = 1e6  # a millisecond
    found = {"count": 2, "device_s": 0.02, "unnamed": 0.0,
             **{s: 0.0 for s in step_scopes.SCOPES}}
    asked = {}

    def phases(ctx, programs, scopes):
        asked.update(programs=programs, scopes=tuple(scopes))
        return found
    monkeypatch.setattr(step_phases, "phases", phases)
    ctx = {"profile_dir": str(tmp_path), "run_dir": str(tmp_path)}
    assert step_scopes.read(ctx, ["jit_conflict_step"], ["clip"]) is None
    assert asked["scopes"][:6] == step_phases.SCOPES
    assert asked["scopes"][6:] == ("clip", "combine")
    found.update(clip=0.0004, combine=0.003)
    assert step_scopes.read(ctx, ["jit_conflict_step"], ["clip"]) == \
        pytest.approx(0.2)
    assert step_scopes.read(ctx, ["jit_conflict_step"], ["combine"]) == \
        pytest.approx(1.5)
    monkeypatch.undo()
    # through the real reduction: two executions, three operations each
    ops = [(0 * ns, 1 * ns, "fusion.1"), (1 * ns, 2 * ns, "fusion.2"),
           (3 * ns, 1 * ns, "all-reduce.1"), (10 * ns, 1 * ns, "fusion.1"),
           (11 * ns, 2 * ns, "fusion.2"), (13 * ns, 3 * ns, "all-reduce.1")]
    maps = [{"fusion.1": "clip", "fusion.2": "sort",
             "all-reduce.1": "combine"}]
    got = step_phases.phase_seconds(
        ops, [(0, 4 * ns, "jit_conflict_step"),
              (10 * ns, 16 * ns, "jit_conflict_step")],
        maps, step_scopes.SCOPES)
    assert got["clip"] == pytest.approx(0.002)
    assert got["combine"] == pytest.approx(0.004)
    assert got["sort"] == pytest.approx(0.004) and got["unnamed"] == 0.0
    # no profile at all
    assert step_scopes.read({"profile_dir": None, "run_dir": str(tmp_path)},
                            ["jit_conflict_step"], ["clip"]) is None


@pytest.mark.parametrize("trace", [False, True])
def test_the_four_resolver_cell_walks_the_partition_on_the_cpu(trace):
    result, lines = run_cell(workload="fdb-write-4res", seed=2_800_000_023,
                             seconds=2.0, trace=trace,
                             env_extra=FOUR_HOST_DEVICES)
    boot = next(line for line in lines if line["line"] == "boot")
    assert boot["Backend"] == "cpux4" and boot["DeviceCount"] == 4
    resolver = next(line for line in lines if line["line"] == "resolver")
    assert resolver["Poisoned"] is False
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 50
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) == wanted("fdb-write-4res", kind)
    if trace:
        m = {k: v["value"] for k, v in result["metrics"].items()}
        assert 100.0 <= m["resolver.shard_skew"] <= 400.0
        assert m["resolver.cut_moves_per_s"] >= 0.0
        assert m["resolver.shard_combine_p50_ms"] > 0.0
        spans = os.path.join(ROOT, ".bench_run", "fdb-write-4res", "spans")
        with open(os.path.join(
                spans, "scopes.conflict_step.320x320.json")) as f:
            scopes = set(json.load(f)["scopes"].values())
        assert {"clip", "combine", "sort", "gc"} <= scopes
        recuts = subprocess.run(
            ["grep", "-l", "Resolver.Recut", "-r", spans],
            capture_output=True, text=True).stdout
        assert recuts, "the load moved no cut: no Resolver.Recut section"
