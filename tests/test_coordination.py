"""Coordination layer: generation-register quorum state + leader election.

Reference semantics under test (Coordination.actor.cpp,
CoordinatedState.actor.cpp, LeaderElection.actor.cpp): quorum reads return the
latest written value; competing writers serialize (one wins, the loser sees
failure); election converges on one leader with a majority; leases expire when
the leader stops renewing; a minority of dead coordinators is tolerated.
"""

import pytest

from foundationdb_tpu.core.eventloop import EventLoop
from foundationdb_tpu.core.sim import KillType, SimNetwork
from foundationdb_tpu.server.coordination import (
    CoordinatedStateClient, Coordinator, elect_leader, get_leader)
from foundationdb_tpu.utils.errors import FDBError
from foundationdb_tpu.utils.rng import DeterministicRandom


def _mk(n_coord=3, seed=1):
    loop = EventLoop()
    net = SimNetwork(loop, DeterministicRandom(seed))
    coords = []
    for i in range(n_coord):
        p = net.new_process(f"coord:{i}")
        Coordinator(p)
        coords.append(p.address)
    return loop, net, coords


def test_coordinated_state_read_write():
    loop, net, coords = _mk()
    client_proc = net.new_process("client:0")
    cs = CoordinatedStateClient(client_proc, coords)
    result = {}

    async def t():
        v0, g0 = await cs.read()
        assert v0 is None
        await cs.write({"epoch": 1, "tlogs": ["a"]})
        v1, g1 = await cs.read()
        result["v"] = v1

    loop.run_future(loop.spawn(t()), max_time=60.0)
    assert result["v"] == {"epoch": 1, "tlogs": ["a"]}


def test_coordinated_state_survives_coordinator_minority_failure():
    loop, net, coords = _mk()
    client_proc = net.new_process("client:0")
    cs = CoordinatedStateClient(client_proc, coords)
    result = {}

    async def t():
        await cs.write({"epoch": 2})
        net.kill(coords[0], KillType.KillProcess)
        v, _ = await cs.read()
        result["v"] = v
        await cs.write({"epoch": 3})
        v2, _ = await cs.read()
        result["v2"] = v2

    loop.run_future(loop.spawn(t()), max_time=60.0)
    assert result["v"] == {"epoch": 2}
    assert result["v2"] == {"epoch": 3}


def test_coordinated_state_majority_failure_blocks():
    loop, net, coords = _mk()
    client_proc = net.new_process("client:0")
    cs = CoordinatedStateClient(client_proc, coords)
    result = {}

    async def t():
        net.kill(coords[0], KillType.KillProcess)
        net.kill(coords[1], KillType.KillProcess)
        try:
            await cs.write({"epoch": 9})
            result["r"] = "wrote"
        except FDBError as e:
            result["r"] = e.name

    loop.run_future(loop.spawn(t()), max_time=60.0)
    assert result["r"] == "coordinators_changed"


def test_competing_writers_serialize():
    loop, net, coords = _mk()
    a = CoordinatedStateClient(net.new_process("writer:a"), coords)
    b = CoordinatedStateClient(net.new_process("writer:b"), coords)
    outcomes = {}

    async def writer(name, cs, value):
        try:
            await cs.write(value)
            outcomes[name] = "ok"
        except FDBError as e:
            outcomes[name] = e.name

    t1 = loop.spawn(writer("a", a, {"who": "a"}))
    t2 = loop.spawn(writer("b", b, {"who": "b"}))
    from foundationdb_tpu.core.future import all_of
    loop.run_future(all_of([t1, t2]), max_time=60.0)
    # both may succeed (serialized one after the other) but the final value
    # must be exactly one of them and reads must agree
    reader = CoordinatedStateClient(net.new_process("reader:0"), coords)
    out = {}

    async def check():
        v, _ = await reader.read()
        out["v"] = v

    loop.run_future(loop.spawn(check()), max_time=60.0)
    assert out["v"] in ({"who": "a"}, {"who": "b"})


def test_leader_election_converges_and_fails_over():
    loop, net, coords = _mk()
    w1 = net.new_process("worker:1")
    w2 = net.new_process("worker:2")
    state = {}

    async def candidate(proc, prio, key):
        await elect_leader(proc, coords, priority=prio, lease_seconds=3.0,
                           poll_interval=0.5)
        state[key] = loop.now()
        # hold the lease by re-electing periodically while alive
        while proc.alive:
            await elect_leader(proc, coords, priority=prio, lease_seconds=3.0,
                               poll_interval=0.5)
            await loop.delay(1.0)

    net.processes["worker:1"].spawn(candidate(w1, 10, "w1_leader"))
    net.processes["worker:2"].spawn(candidate(w2, 5, "w2_leader"))
    client = net.new_process("client:0")
    seen = {}

    async def observe():
        await loop.delay(2.0)
        seen["first"] = await get_leader(client, coords)
        net.kill("worker:1", KillType.KillProcess)
        await loop.delay(8.0)  # lease expires, lower-priority takes over
        seen["second"] = await get_leader(client, coords)

    loop.run_future(loop.spawn(observe()), max_time=120.0)
    assert seen["first"] == "worker:1"  # higher priority wins
    assert seen["second"] == "worker:2"  # failover after lease expiry


def test_a_killed_candidate_stops_campaigning():
    """A kill that lands while the candidate waits for a coordinator's
    answer cancels it there; the campaign must end with its process and
    not go on as an actor of no process, which a reboot would let win an
    election with no lease-keeper beside it (a cluster controller that
    locks every new generation's logs and is never deposed)."""
    loop, net, coords = _mk()
    w = net.new_process("worker:1")
    for c in coords:  # the candidacy requests stay in flight
        net.clog_pair("worker:1", c, 5.0)
    task = w.spawn(elect_leader(w, coords, priority=1), "ccCandidate")

    async def kill_then_wait():
        await loop.delay(1.0)
        net.kill("worker:1", KillType.KillProcess)
        await loop.delay(10.0)

    loop.run_future(loop.spawn(kill_then_wait()), max_time=60.0)
    assert task.is_ready() and task.is_error()
    assert task._result.name == "operation_cancelled"
