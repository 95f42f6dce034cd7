"""The two readers of PR 26 — `gap_cause` (why the device was idle) and
`step_phases` (the step program's device time by scope): the reductions on
intervals worked by hand, and both readers over a small recorded trace with
its span files, cut from a traced `fdb-write` run of PR 26 on the chip (TPU
v5 lite) by trim_timeline.py."""

import gzip
import os
import random
import shutil
import tarfile

import pytest

from conftest import BENCH
from readers import gap_cause, step_phases, xplane

DATA = os.path.join(BENCH, "tests", "data")
RECORDED = os.path.join(DATA, "fdb-write.pr26.trimmed.xplane.pb.gz")
RECORDED_SPANS = os.path.join(DATA, "fdb-write.pr26.trimmed.spans.tgz")
PROGRAMS = ["jit__unknown", "jit_conflict_step", "jit__conflict_step"]


# ----------------------------------------------------------- intervals

def test_interval_arithmetic():
    u = gap_cause.union
    assert u([(5, 9), (0, 2), (1, 3), (9, 10), (4, 4)]) == [(0, 3), (5, 10)]
    a, b = [(0, 10), (20, 30)], [(5, 22), (25, 26), (40, 50)]
    assert gap_cause.intersect(a, b) == [(5, 10), (20, 22), (25, 26)]
    assert gap_cause.subtract(a, b) == [(0, 5), (22, 25), (26, 30)]
    assert gap_cause.subtract(a, []) == a
    assert gap_cause.subtract(a, [(0, 100)]) == []
    assert gap_cause.length(a) == 20
    assert gap_cause.idle_intervals([(10, 20), (15, 30), (50, 60)],
                                    (0, 100)) == [(0, 10), (30, 50), (60, 100)]


def test_causes_take_an_idle_instant_in_their_order():
    idle = [(0, 100)]
    dispatch, pending, assembling = [(10, 30)], [(0, 40)], [(20, 70)]
    got = gap_cause.attribute(idle, dispatch, pending, assembling)
    assert got == {"dispatch": [(10, 30)],            # wins over both others
                   "pending": [(0, 10), (30, 40)],    # where no dispatch is
                   "assembling": [(40, 70)],          # where neither is
                   "no_work": [(70, 100)]}


def test_a_gap_straddling_two_causes_is_cut_at_the_boundary():
    # one gap 100..200; the proxy held a batch until 130, its resolve began
    # there and reached the resolver at 160, whose dispatch ran until 175
    got = gap_cause.attribute([(100, 200)], dispatch=[(160, 175)],
                              pending=[(130, 160)], assembling=[(90, 130)])
    assert {c: gap_cause.length(v) for c, v in got.items()} == {
        "dispatch": 15, "pending": 30, "assembling": 30, "no_work": 25}
    # busy time is nobody's: a span over a busy stretch adds nothing
    got = gap_cause.attribute([(0, 10), (20, 30)], [], [(5, 25)], [])
    assert got["pending"] == [(5, 10), (20, 25)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_four_causes_add_up_to_the_idle_time(seed):
    rng = random.Random(seed)

    def some(n, longest):
        out = []
        for _ in range(n):
            start = rng.uniform(0, 1000)
            out.append((start, start + rng.uniform(0, longest)))
        return out

    idle = gap_cause.idle_intervals(some(12, 60), (0, 1000))
    parts = gap_cause.attribute(idle, some(20, 5), some(20, 30), some(20, 80))
    assert sum(map(gap_cause.length, parts.values())) == pytest.approx(
        gap_cause.length(idle))
    pieces = sorted(p for v in parts.values() for p in v)
    assert all(a[1] <= b[0] + 1e-9 for a, b in zip(pieces, pieces[1:]))


def test_the_clock_offset_is_recovered_from_jittered_readings():
    rng = random.Random(7)
    offset = 1.7e18 - 4.2e13  # profiler ns minus monotonic ns
    readings = []
    for i in range(101):
        mono_us = 5.0e10 + i * 40_000
        # the annotation opens a little after time.monotonic was read; one
        # in ten a good deal later (a descheduled thread)
        late = rng.uniform(200, 900) + (rng.random() < 0.1) * 250_000
        readings.append((mono_us * 1e3 + offset + late, mono_us))
    got = gap_cause.clock_offset_ns(readings)
    assert 0 <= got - offset < 1_000  # within a microsecond, never early
    assert gap_cause.clock_offset_ns([]) is None


def test_the_devices_lead_is_the_largest_and_never_negative():
    # (device start, host launch): two launches onto an idle device lead by
    # 2.0 and 1.9 ms, one queued 30 ms behind another
    launches = [(100.0e6, 102.0e6), (200.0e6, 201.9e6), (330.0e6, 300.0e6)]
    assert gap_cause.device_lead_ns(launches) == pytest.approx(2.0e6)
    # a device that was never idle: every run queued, which says nothing
    assert gap_cause.device_lead_ns([(330.0e6, 300.0e6)]) == 0.0
    assert gap_cause.device_lead_ns([]) == 0.0


def test_pending_runs_from_the_resolves_begin_to_its_dispatch():
    spans = {"Proxy.Resolve": [("b0.1", 1.0, 1.5), ("b0.2", 2.0, 2.5),
                               ("b0.3", 3.0, 3.5)],
             "Resolver.Dispatch": [("v10", 1.2, 1.21), ("v20", 2.6, 2.61)]}
    attach = {"c1.1": ["b0.1"], "b0.1": ["v10"], "b0.2": ["v20"]}
    assert gap_cause.pending_intervals(spans, attach) == [
        (1.0, 1.2),    # until its own dispatch began
        (2.0, 2.5),    # a dispatch after the resolve's end does not stretch it
        (3.0, 3.5)]    # no dispatch in the files: until the resolve ended


# ------------------------------------------------------ the step's phases

def test_an_operation_is_named_by_its_instruction():
    # the scope maps' keys are what xplane.short_name makes of an event
    assert xplane.short_name(
        "%fusion.74 = s32[160]{0:T(256)} fusion(s32[256]{0} %copy-done), "
        "kind=kCustom, calls=%fused_computation.74") == "fusion.74"
    assert xplane.short_name("%while.7 = (s32[]) while(%t)") == "while.7"


def test_an_execution_is_read_with_the_map_that_knows_it_best():
    small = {"fusion.1": "sort", "fusion.2": "gc"}
    large = {"fusion.1": "intra", "fusion.2": "gc", "fusion.300": "table"}
    assert step_phases.best_map({"fusion.1", "fusion.300", "copy.3"},
                                [small, large]) is large
    assert step_phases.best_map({"fusion.1"}, [small, large]) is small
    assert step_phases.best_map({"x"}, []) == {}


def test_an_events_own_time_leaves_out_what_it_holds():
    # a while of 100 with two bodies of 30 inside, one of which holds 10
    events = [(0, 100, "while"), (10, 30, "body1"), (15, 10, "inner"),
              (50, 30, "body2"), (100, 20, "after")]
    got = dict((tag, own) for own, tag in step_phases.own_times(events))
    assert got == {"while": 40, "body1": 20, "inner": 10, "body2": 30,
                   "after": 20}
    assert sum(got.values()) == 120  # the union's length: nothing twice
    # an event without a tag belongs to what holds it; at the top, to nobody
    got = step_phases.own_times([(0, 100, "sort"), (10, 30, None),
                                 (15, 10, None), (100, 5, None)])
    assert sorted(got, key=str) == sorted(
        [(70, "sort"), (20, "sort"), (10, "sort"), (5, None)], key=str)


def test_phase_sums_are_the_programs_time_less_the_unnamed_part():
    ms = 1e6
    step, other = "jit_conflict_step(1)", "jit_conflict_step(2)"
    run1, run2 = (0, 40 * ms, step), (100 * ms, 140 * ms, step)
    run3 = (150 * ms, 160 * ms, other)  # the other bucket's program
    scopes = {"while.7": "sort", "fusion.264": "sort", "fusion.68": "history",
              "fusion.9": "intra", "fusion.118": "merge", "fusion.149": "gc",
              "fusion.5": "table"}
    other_scopes = {"while.7": "intra", "fusion.1000": "table"}
    ops = []
    for start, _end, _p in (run1, run2):
        ops += [(start, 18 * ms, "while.7"),
                (start + 1 * ms, 8 * ms, "fusion.264"),
                (start + 9 * ms, 1 * ms, "copy.1"),     # in the while: sort's
                (start + 18 * ms, 6 * ms, "fusion.68"),
                (start + 24 * ms, 4 * ms, "fusion.9"),
                (start + 28 * ms, 5 * ms, "fusion.118"),
                (start + 33 * ms, 3 * ms, "fusion.149"),
                (start + 36 * ms, 2 * ms, "fusion.5"),
                (start + 38 * ms, 1 * ms, "copy.2")]    # nobody's
    ops += [(60 * ms, 1 * ms, "fusion.9"),              # outside every run
            (150 * ms, 6 * ms, "while.7"), (156 * ms, 2 * ms, "fusion.1000"),
            (191 * ms, 5 * ms, "while.7")]              # in a cut run
    got = step_phases.phase_seconds(ops, [run1, run2, run3],
                                    [other_scopes, scopes])
    assert got == pytest.approx({
        "count": 3, "device_s": 0.086, "unnamed": 0.002, "sort": 0.036,
        "history": 0.012, "intra": 0.014, "merge": 0.010, "gc": 0.006,
        "table": 0.006})
    named = sum(got[s] for s in step_phases.SCOPES)
    assert named == pytest.approx(got["device_s"] - got["unnamed"])
    assert step_phases.phase_seconds(ops, [], [scopes]) is None
    # without a map everything is unnamed, never lost
    bare = step_phases.phase_seconds(ops, [run1], [])
    assert bare["unnamed"] == pytest.approx(bare["device_s"]) == \
        pytest.approx(0.039)


def test_whole_executions_follow_xplanes_rule():
    modules = [("jit_conflict_step(1)", 0.0, 5e6), ("jit_conflict_step(1)", 1e7, 3e7),
               ("jit_combine_status(2)", 5e7, 1e3), ("jit_conflict_step(1)", 9.5e7, 5e6)]
    assert step_phases.whole_executions(
        modules, PROGRAMS, (0.0, 1e8)) == [(1e7, 4e7, "jit_conflict_step(1)")]


# ------------------------------------------------ over the recorded trace

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A run directory as run.py leaves it: profile/ and spans/."""
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace")
    run_dir = tmp_path_factory.mktemp("run")
    profile = run_dir / "profile" / "plugins" / "profile" / "x"
    profile.mkdir(parents=True)
    with gzip.open(RECORDED, "rb") as src, \
            open(profile / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with tarfile.open(RECORDED_SPANS) as tar:  # spans/: records, scope maps
        tar.extractall(run_dir, filter="data")
    return {"profile_dir": str(run_dir / "profile"), "run_dir": str(run_dir)}


def test_gap_cause_over_the_recorded_trace(recorded):
    got = gap_cause.causes(recorded)
    assert got["readings"] >= 10
    idle_share = xplane.read(recorded, idle_share=True)
    shares = {c: gap_cause.read(recorded, cause=c) for c in gap_cause.CAUSES}
    assert all(v is not None and v >= 0 for v in shares.values())
    assert sum(shares.values()) == pytest.approx(idle_share, abs=1e-6)
    assert got["idle_s"] / got["window_s"] * 100 == pytest.approx(idle_share)
    # the tie holds: every dispatch section's annotation lies, through the
    # offset, on its span records to within a few tens of microseconds
    notes = [n for n in gap_cause.load_timeline(
        xplane.find_trace(recorded["profile_dir"]))["notes"] if n[0] == "Resolver.Dispatch"]
    spans, _attach = gap_cause.load_spans(
        os.path.join(recorded["run_dir"], "spans"))
    begins = sorted(b for _i, b, _e in spans["Resolver.Dispatch"])
    assert notes
    for _name, start_ns, _dur, _us in notes:
        on_mono = (start_ns - got["offset_ns"]) / 1e9
        assert min(abs(on_mono - b) for b in begins) < 200e-6
    gap = got["gaps"][0]
    assert gap["seconds"] == pytest.approx(sum(gap["by_cause"].values()))
    assert gap["open"], "the longest gap has spans open in it"
    # the stretch's known numbers: the device stood idle 37.4% of it, nearly
    # all of that with a batch held by the proxy
    assert got["device_lead_ns"] == pytest.approx(876740.0)
    assert shares == pytest.approx({
        "dispatch": 0.934670, "pending": 0.570606, "assembling": 35.917383,
        "no_work": 0.019011}, rel=1e-4)
    assert [row[0] for row in gap["open"][:3]] == [
        "Proxy.QueueDelay", "Proxy.Resolve", "Resolver.Readback"]


def test_step_phases_over_the_recorded_trace(recorded):
    got = step_phases.phases(recorded, PROGRAMS)
    assert got["count"] >= 1
    step_ms = xplane.read(recorded, programs=PROGRAMS)
    by_scope = {s: step_phases.read(recorded, programs=PROGRAMS, scopes=[s])
                for s in step_phases.SCOPES}
    assert all(v is not None and v > 0 for v in by_scope.values())
    unnamed = step_phases.read(recorded, programs=PROGRAMS, unnamed_share=True)
    assert 0 <= unnamed < 5.0
    # the operations' own times fill the program's executions: what is
    # missing is the idle time between operations inside a program
    total = sum(by_scope.values()) / (1 - unnamed / 100)
    assert total <= step_ms * 1.0001
    assert total == pytest.approx(step_ms, rel=0.02)
    assert got["count"] == 3
    assert by_scope == pytest.approx({
        "sort": 27.585313, "history": 0.018635, "intra": 3.279480,
        "merge": 1.254225, "gc": 7.941430, "table": 0.071827}, rel=1e-5)
    assert unnamed == pytest.approx(0.852483, rel=1e-5)


def test_a_run_without_the_timeline_reads_nothing(tmp_path):
    """No profile; or the parent's: no annotation, no scope. Never a zero."""
    for ctx in ({"profile_dir": None, "run_dir": str(tmp_path)},
                {"profile_dir": str(tmp_path), "run_dir": str(tmp_path)}):
        assert gap_cause.read(ctx, cause="dispatch") is None
        assert step_phases.read(ctx, programs=PROGRAMS, scopes=["sort"]) is None
    old = os.path.join(DATA, "fdb-write.trimmed.xplane.pb.gz")  # PR 25's
    profile = tmp_path / "profile" / "plugins" / "profile" / "x"
    profile.mkdir(parents=True)
    with gzip.open(old, "rb") as src, \
            open(profile / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    (tmp_path / "spans").mkdir()
    ctx = {"profile_dir": str(tmp_path / "profile"), "run_dir": str(tmp_path)}
    assert xplane.read(ctx, programs=PROGRAMS) is not None
    for cause in gap_cause.CAUSES:
        assert gap_cause.read(ctx, cause=cause) is None
    assert step_phases.read(ctx, programs=PROGRAMS, scopes=["sort"]) is None
    assert step_phases.read(ctx, programs=PROGRAMS, unnamed_share=True) is None
