"""Graded randomized-simulation tiers (testing/simulated_cluster).

Fast tier: a bounded seeded sweep — every seed draws its own cluster
(topology, replication mode, storage engine, conflict backend, buggified
knobs) exactly like SimulatedCluster.actor.cpp:1239, then runs one randomly
picked fast spec against it. Plus one pinned (seed, spec) pair per fast spec
so every workload in the battery provably runs alongside a fault workload in
tier-1, whatever the sweep happens to draw.

Slow tier (pytest -m slow): the long compositions — backup under attrition,
the swizzled battery, two-region fuzz.

Every failure surfaces a one-line repro command in the pytest report via
SpecFailure's message (run_randomized_spec prints it too).
"""

import pytest

from foundationdb_tpu.testing import simulated_cluster as SC

# Pinned sweep seeds: verified to pass AND to draw pairwise-distinct
# (topology, replication, engine, backend, knobs) tuples covering single /
# double / two-region replication, all three engines, and all three default
# backends. If a code change makes one fail, the printed repro line replays
# it. (Re-picked when DEFAULT_BACKENDS grew sharded: widening an allow-list
# shifts every downstream randint for every seed. Since PR 30 a knob's draw
# depends on the seed and the knob's own name alone, so registering or
# deleting a knob moves no other; that change re-rolled every seed once and
# moved 4 -> 28, 46 -> 23 and fuzz-api's 19 -> 20, each to the nearest seed
# of the same replication/engine/backend that passes: ROADMAP D11, D14.)
FAST_SWEEP_SEEDS = [1, 2, 3, 5, 7, 8, 10, 13, 15, 19, 23, 25, 28, 38]

# One pinned pair per fast spec (seed drawn compatible with the spec's
# needs): the guarantee that EVERY workload — fuzz battery and deepened
# ConflictRange included — exercises at least one spec with faults in
# tier-1. Mostly-oracle draws for cheapness; cycle deliberately pins a
# SHARDED draw so the SPMD mesh path runs under faults in tier-1 even if
# the sweep's wall-clock budget skips its sharded seeds.
PINNED_FAST = [
    ("cycle", 15),            # single/memory/sharded
    ("zipfian-hotkey", 2),    # single/memory/oracle (needs flat)
    ("zipfian-read-hotspot", 25),  # double/memory/oracle (needs flat):
    # the 2-replica draw, so the hedged multi-replica client path serves
    # the skewed readers through clogging + attrition

    ("conflict-range", 2),    # single/memory/oracle
    ("fuzz-api", 20),         # single/redwood/oracle
    ("serializability", 23),  # single/ssd/oracle
    ("ryow", 22),             # single/memory/oracle
    ("change-config", 33),    # double/redwood/oracle (needs flat)
    ("remove-servers", 36),   # double/memory/device + spare storage
    ("kill-region", 49),      # two_region/ssd/oracle
]

PINNED_SLOW = [
    ("backup-attrition", 24),  # single/redwood/oracle (needs flat)
    ("swizzled-battery", 25),  # double/memory/oracle
    ("two-region-fuzz", 43),   # two_region/redwood/oracle
]


def test_fast_sweep_draws_are_distinct_and_cover_the_axes():
    """Pure draw check (no clusters booted): the sweep seeds below must
    draw pairwise-distinct environment tuples and between them cover every
    replication mode, all three storage engines, and all three default
    backends."""
    draws = [SC.ClusterDraw.draw(s) for s in FAST_SWEEP_SEEDS]
    tuples = {d.distinct_tuple() for d in draws}
    assert len(tuples) == len(draws), "sweep seeds drew duplicate clusters"
    assert len(draws) >= 12
    assert {d.replication for d in draws} == \
        {"single", "double", "two_region"}
    assert {d.storage_engine for d in draws} == {"memory", "ssd", "redwood"}
    assert {d.conflict_backend for d in draws} == \
        {"oracle", "device", "sharded"}


def test_fast_tier_sweep():
    """The CI sweep: run the fast tier over the pinned seeds under a wall
    clock cap. At least 12 seeds must complete (a too-slow environment
    fails loudly instead of eating the whole tier-1 budget), and the draws
    that ran must be pairwise distinct — asserted on the RESULTS, not just
    the seed list."""
    results = SC.sweep(FAST_SWEEP_SEEDS, tier="fast",
                       wall_clock_budget=420.0)
    assert len(results) >= 12, \
        f"only {len(results)} sweep seeds finished inside the budget"
    tuples = {r.draw.distinct_tuple() for r in results}
    assert len(tuples) == len(results)


@pytest.mark.parametrize("spec_name,seed", PINNED_FAST,
                         ids=[s for s, _ in PINNED_FAST])
def test_fast_spec(spec_name, seed):
    r = SC.run_randomized_spec(seed, spec=spec_name)
    assert r.spec == spec_name
    assert r.result.elapsed > 0


@pytest.mark.slow
@pytest.mark.parametrize("spec_name,seed", PINNED_SLOW,
                         ids=[s for s, _ in PINNED_SLOW])
def test_slow_spec(spec_name, seed):
    r = SC.run_randomized_spec(seed, spec=spec_name)
    assert r.spec == spec_name


def test_spec_failure_carries_the_repro_line():
    """Any failing spec must surface the one-line repro command in the
    exception pytest reports (and print it): inject a spec whose check
    always fails and assert the repro format."""
    from foundationdb_tpu.testing.workloads import Workload

    class AlwaysFails(Workload):
        name = "AlwaysFails"

        async def check(self, db):
            raise AssertionError("injected failure")

    spec = SC.Spec("always-fails", "fast", lambda rng: [AlwaysFails()],
                   duration=2.0)
    with pytest.raises(SC.SpecFailure) as ei:
        SC.run_randomized_spec(2, spec=spec,
                               allow_backends=("oracle",))
    msg = str(ei.value)
    assert "--seed 2 --spec always-fails" in msg
    assert "python -m foundationdb_tpu.testing.simulated_cluster" in msg
    assert "drew:" in msg


def test_incompatible_explicit_spec_is_rejected():
    """Asking for a two-region spec on a seed that drew a flat cluster is a
    usage error, not a silent re-draw (the repro line must stay honest)."""
    d = SC.ClusterDraw.draw(2)
    assert d.replication != "two_region"
    with pytest.raises(ValueError):
        SC.run_randomized_spec(2, spec="kill-region")
