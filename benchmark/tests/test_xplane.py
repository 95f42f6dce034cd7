"""The reduction from a profiler trace to device numbers, on intervals worked
by hand and on a small recorded trace: the first quarter second of a trace
taken inside the core process on the chip (PR 25, `fdb-write`, TPU v5 lite),
cut down by trim_xplane.py."""

import gzip
import os

import numpy as np
import pytest

from conftest import BENCH
from readers import roofline, xplane

RECORDED = os.path.join(BENCH, "tests", "data",
                        "fdb-write.trimmed.xplane.pb.gz")
STEP = "jit__unknown(14322697176650784785)"
CONFIG = {"knobs": {"CONFLICT_STATE_CAPACITY": 262144,
                    "CONFLICT_BATCH_TXNS": 256,
                    "CONFLICT_BATCH_READS_PER_TXN": 10,
                    "CONFLICT_BATCH_WRITES_PER_TXN": 10}}


def test_busy_is_the_union_of_the_intervals():
    assert xplane.busy_union_ns([]) == 0
    assert xplane.busy_union_ns([(0, 10)]) == 10
    assert xplane.busy_union_ns([(0, 10), (20, 30)]) == 20       # apart
    assert xplane.busy_union_ns([(0, 10), (5, 15)]) == 15        # overlapping
    assert xplane.busy_union_ns([(0, 30), (5, 15)]) == 30        # nested
    assert xplane.busy_union_ns([(0, 10), (10, 20)]) == 20       # touching
    assert xplane.busy_union_ns([(20, 30), (0, 10), (8, 22)]) == 30  # unsorted


def test_gaps_are_named_by_what_ran_before_them():
    got = xplane.gaps([(10, 20, "a"), (50, 60, "b"), (55, 58, "c")],
                      (0, 100))
    assert got == [(40, "b"), (30, "a"), (10, "window_open")]
    assert xplane.gaps([], (0, 7)) == [(7, "window_open")]


def test_reduce_on_hand_made_planes():
    ms = 1e6
    trace = {"span_ns": (0.0, 100 * ms), "devices": {
        "/device:TPU:0": {
            "XLA Ops": [("%a = f32[] add()", 10 * ms, 20 * ms),
                        ("%b = f32[] mul()", 25 * ms, 15 * ms),
                        ("%a = f32[] add()", 60 * ms, 10 * ms)],
            "XLA Modules": [("jit_step(1)", 0.0, 5 * ms),     # cut by the start
                            ("jit_step(1)", 10 * ms, 30 * ms),
                            ("jit_step(1)", 60 * ms, 10 * ms),
                            ("jit_other(2)", 80 * ms, 1 * ms),
                            ("jit_step(1)", 95 * ms, 5 * ms)]},   # by the end
        "/device:TPU:1": {"XLA Ops": [("%a = f32[] add()", 0.0, 20 * ms)],
                          "XLA Modules": []}}}
    red = xplane.reduce(trace)
    assert red["window_s"] == pytest.approx(0.1)
    # chip 0 is busy 10..40 and 60..70 = 40 ms, chip 1 20 ms: the mean
    assert red["busy_s"] == pytest.approx(0.030)
    assert red["programs"] == {"jit_step(1)": [pytest.approx(0.040), 2],
                               "jit_other(2)": [pytest.approx(0.001), 1]}
    assert xplane.program_seconds(red, ["jit_step"]) == (
        pytest.approx(0.040), 2)
    assert xplane.program_seconds(red, ["jit_st"]) == (0.0, 0)
    assert red["ops"]["%a = f32[] add()"] == [pytest.approx(0.050), 3]
    assert red["gaps"][0] == (30 * ms, "%a = f32[] add()")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    return str(path)


def test_the_recorded_trace_reduces_to_its_known_numbers(recorded):
    trace = xplane.load(recorded)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    lines = trace["devices"]["/device:TPU:0"]
    assert {"XLA Ops", "XLA Modules"} <= set(lines)
    first, last = trace["span_ns"]
    red = xplane.reduce(trace)
    assert red["window_s"] == pytest.approx((last - first) / 1e9)
    assert red["window_s"] == pytest.approx(0.2480512, rel=1e-6)
    # the step program ran three times in the cut; the first was under way
    # when the trace began and the last when it was cut: one whole execution
    runs = [(s, d) for n, s, d in lines["XLA Modules"] if n == STEP]
    assert len(runs) == 3
    assert red["programs"][STEP] == [pytest.approx(0.040929064), 1]
    # busy, by another road: paint every operation onto a line of microseconds
    ops = lines["XLA Ops"]
    paint = np.zeros(int((last - first) / 1e3) + 2, dtype=bool)
    for _name, s, d in ops:
        paint[int((s - first) / 1e3):int((s + d - first) / 1e3) + 1] = True
    assert red["busy_s"] == pytest.approx(paint.sum() / 1e6, rel=0.02)
    assert red["busy_s"] == pytest.approx(0.068963418, rel=1e-6)
    # no operation outside a program: the programs' time covers the busy time
    covered = xplane.busy_union_ns(
        [(s, s + d) for _n, s, d in lines["XLA Modules"]]) / 1e9
    assert red["busy_s"] <= covered * 1.0001


def test_the_metric_readers_on_the_recorded_trace(recorded, tmp_path):
    profile = tmp_path / "profile" / "plugins" / "profile" / "x"
    profile.mkdir(parents=True)
    os.link(recorded, profile / "host.xplane.pb")
    ctx = {"profile_dir": str(tmp_path / "profile"), "config": CONFIG,
           "device": {"kind": "TPU v5 lite"}}
    names = ["jit__unknown", "jit_conflict_step"]
    assert xplane.read(ctx, programs=names) == pytest.approx(40.929064)
    assert xplane.read(ctx, idle_share=True) == pytest.approx(
        100 * (1 - 0.068963418 / 0.2480512))
    # 56,932,631 bytes at 819 GB/s are 69.5 us of a 40.9 ms step
    assert roofline.read(ctx, programs=names, cost="conflict_step") == \
        pytest.approx(100 * (56_932_631 / 819e9) / 0.040929064)
    assert xplane.read(ctx, programs=["jit_nothing_of_that_name"]) is None
    ctx_unknown = dict(ctx, device={"kind": "TPU v9"})
    with pytest.raises(KeyError):
        roofline.read(ctx_unknown, programs=names, cost="conflict_step")
    summary = xplane.device_summary(ctx)
    assert summary["device"] == {"busy_s": pytest.approx(0.068963418),
                                 "window_s": pytest.approx(0.2480512)}
    ops, idle = (summary["breakdown"][k] for k in ("device_ops", "idle_gaps"))
    assert 0 < len(ops) <= 10 and 0 < len(idle) <= 10
    assert all(len(name) <= 90 and " = " not in name for name, _s in ops + idle)
    assert ops == sorted(ops, key=lambda t: -t[1])
    assert idle[0][1] == pytest.approx(0.130385888)


def test_no_profile_reads_nothing_and_never_zero(tmp_path):
    for ctx in ({"profile_dir": None}, {"profile_dir": str(tmp_path)}):
        assert xplane.read(ctx, idle_share=True) is None
        assert xplane.read(ctx, programs=["jit__unknown"]) is None
        assert xplane.device_summary(ctx) == {}
