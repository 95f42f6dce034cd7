"""The step program's device time by phase.

`ops/conflict.py:conflict_step` runs its numbered phases inside
`jax.named_scope`s (`sort`, `history`, `intra`, `merge`, `gc`, `table`), so
every instruction of the compiled program carries
`op_name="jit(conflict_step)/<scope>/..."`. The profiler's trace of this chip
does not: an event of the device's operations line is named by its
instruction's text (`%fusion.74 = s32[160]... fusion(...)`) and has no stat
but its time (seen in PR 26's traces). So a server that writes span files
also writes, beside them, `scopes.<program>.<bucket>.json` for each bucket
program: {"scopes": {instruction: scope}} from the compiled module's text
(`DeviceConflictSet.write_scope_maps`). A program's executions in the trace
are read with the map that knows most of their operations.

This reader sums, over the whole executions of the step programs (as
`xplane.reduce` counts them), each operation's own time under its scope: a
`while` holds its body's operations as nested events, so an event's own time
is its duration less the events inside it, and an operation without a scope
of its own inside one that has a scope belongs to that scope. What is left
has no scope: `unnamed`.

A run without scope maps (a parent of the PR that added them) reads None:
there is nothing to split.

Loading is kept apart from the reduction, which works on plain tuples.
"""

import glob
import json
import os

from readers import xplane

SCOPES = ("sort", "history", "intra", "merge", "gc", "table")
MAP_FILES = "scopes.*.json"

_cache: dict = {}


def best_map(names: set[str], maps: list[dict]) -> dict:
    """The scope map that knows most of `names` (an execution's
    operations); none is the empty map."""
    return max(maps, key=lambda m: len(names & m.keys()), default={})


def own_times(events: list[tuple[float, float, object]]) -> list[tuple]:
    """(own duration, tag) for (start, duration, tag) events of one line,
    where an event may hold others: each instant belongs to the innermost
    event over it, and an event tagged None takes the tag of the event that
    holds it."""
    out, stack = [], []  # stack of [end, own, tag]

    def close(until: float):
        while stack and stack[-1][0] <= until:
            _end, own, tag = stack.pop()
            out.append((own, tag))

    for start, dur, tag in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][0])  # a child never outlasts its parent
            stack[-1][1] -= end - start
            if tag is None:
                tag = stack[-1][2]
        stack.append([end, end - start, tag])
    close(float("inf"))
    return out


def phase_seconds(ops: list[tuple], executions: list[tuple],
                  maps: list[dict], scopes=SCOPES) -> dict | None:
    """{"count", "device_s", "unnamed", scope: seconds}: the own seconds of
    the (start ns, duration ns, instruction) `ops` inside the (start, end,
    program) `executions`, by scope; each program's operations are read
    with the map of `maps` that knows most of them."""
    if not executions:
        return None
    executions = sorted(executions)
    inside: list[list] = [[] for _ in executions]
    i = 0
    for start, dur, name in sorted(ops, key=lambda o: o[0]):
        while i < len(executions) and executions[i][1] <= start:
            i += 1
        if i < len(executions) and executions[i][0] <= start \
                and start + dur <= executions[i][1]:
            inside[i].append((start, dur, name))
    chosen: dict = {}
    out = {"count": len(executions), "device_s": 0.0, "unnamed": 0.0}
    out.update({s: 0.0 for s in scopes})
    for (_s, _e, program), events in zip(executions, inside):
        if program not in chosen:
            chosen[program] = best_map({n for _s, _d, n in events}, maps)
        scope_of = chosen[program]
        for own, scope in own_times([(s, d, scope_of.get(n))
                                     for s, d, n in events]):
            out["device_s"] += own / 1e9
            out[scope if scope in scopes else "unnamed"] += own / 1e9
    return out


def load_ops(path: str, device_plane: str, ops_line: str,
             modules_line: str) -> dict:
    """{"ops": [(start, duration, instruction)], "modules": [(name, start,
    duration)], "span_ns"} of the first device plane."""
    key = (path, device_plane, ops_line, modules_line)
    if key not in _cache:
        trace = xplane.load(path, device_plane)
        lines = next(iter(sorted(trace["devices"].items())), (None, {}))[1]
        _cache[key] = {
            "ops": [(s, d, xplane.short_name(n))
                    for n, s, d in lines.get(ops_line, [])],
            "modules": lines.get(modules_line, []),
            "span_ns": trace["span_ns"]}
    return _cache[key]


def load_maps(span_dir: str, pattern: str = MAP_FILES) -> list[dict]:
    maps = []
    for path in sorted(glob.glob(os.path.join(span_dir, pattern))):
        with open(path) as f:
            maps.append(json.load(f)["scopes"])
    return maps


def whole_executions(modules: list[tuple], programs: list[str],
                     span_ns: tuple) -> list[tuple[float, float, str]]:
    """(start, end, program) of the programs' executions not cut by the
    trace's edges: the same rule as `xplane.reduce`."""
    first, last = span_ns
    return [(s, s + d, name) for name, s, d in modules
            if name.split("(", 1)[0] in programs
            and s > first + xplane.EDGE_NS and s + d < last - xplane.EDGE_NS]


def phases(ctx: dict, programs: list[str],
           device_plane: str = xplane.DEVICE_PLANE,
           ops_line: str = xplane.OPS_LINE,
           modules_line: str = xplane.MODULES_LINE,
           map_files: str = MAP_FILES, scopes=SCOPES) -> dict | None:
    if not ctx.get("profile_dir"):
        return None
    path = xplane.find_trace(ctx["profile_dir"])
    maps = load_maps(os.path.join(ctx["run_dir"], "spans"), map_files)
    if path is None or not maps:
        return None  # no profile, or a program that names no scope
    trace = load_ops(path, device_plane, ops_line, modules_line)
    got = phase_seconds(trace["ops"], whole_executions(
        trace["modules"], programs, trace["span_ns"]), maps, tuple(scopes))
    if got is None or got["device_s"] <= 0:
        return None
    return got


def read(ctx: dict, programs: list[str], scopes: list[str] | None = None,
         unnamed_share: bool = False, **names) -> float | None:
    """ms an execution under `scopes` (summed), or with `unnamed_share` the
    share (%) of the programs' device time under no scope."""
    got = phases(ctx, programs, **names)
    if got is None:
        return None
    if unnamed_share:
        return 100.0 * got["unnamed"] / got["device_s"]
    return 1e3 * sum(got[s] for s in scopes) / got["count"]
