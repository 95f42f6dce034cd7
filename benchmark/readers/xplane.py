"""The reduction of a `jax.profiler` trace (`*.xplane.pb`, taken inside the
core process, which holds the chip) to device numbers:

- busy seconds: the union of the intervals in which an operation ran on a
  device, averaged over the device planes;
- the traced window: from the first to the last event of any plane;
- a program's time: the mean device duration of the executions of the
  program whose name a metric's file gives.

Which plane is a device and which of its lines hold operations and programs
are names found by looking at a trace of this chip; they are arguments with
these defaults, and a metric's file may give others.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
EDGE_NS = 1000.0  # an execution this near the trace's edge was cut by it

_cache: dict = {}


def find_trace(profile_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str, device_plane: str = DEVICE_PLANE) -> dict:
    """{"devices": {plane: {line: [(name, start_ns, duration_ns)]}},
    "span_ns": (first start, last end) over every plane}."""
    key = (path, device_plane)
    if key in _cache:
        return _cache[key]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    first, last = None, None
    for plane in data.planes:
        is_device = plane.name.startswith(device_plane)
        lines = {}
        for line in plane.lines:
            rows = []
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                if first is None or start < first:
                    first = start
                if last is None or start + dur > last:
                    last = start + dur
                if is_device:
                    rows.append((e.name, start, dur))
            if is_device:
                lines[line.name] = rows
        if is_device:
            devices[plane.name] = lines
    out = {"devices": devices, "span_ns": (first, last)}
    _cache[key] = out
    return out


def busy_union_ns(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def gaps(intervals: list[tuple[float, float, str]], span: tuple) -> list:
    """Idle gaps (seconds, name of the operation that ended before it),
    longest first, the window's edges included."""
    out, edge, before = [], span[0], "window_open"
    for start, end, name in sorted(intervals):
        if start > edge:
            out.append((start - edge, before))
        if end > edge:
            edge, before = end, name
    if span[1] > edge:
        out.append((span[1] - edge, before))
    return sorted(out, reverse=True)


def reduce(trace: dict, ops_line: str = OPS_LINE,
           modules_line: str = MODULES_LINE) -> dict:
    """busy_s (mean over devices), window_s, and per device plane the
    operations and programs with their summed seconds and counts."""
    first, last = trace["span_ns"]
    out = {"window_s": (last - first) / 1e9 if first is not None else 0.0,
           "busy_s": None, "ops": {}, "programs": {}, "gaps": []}
    busy = []
    for plane, lines in sorted(trace["devices"].items()):
        ops = lines.get(ops_line, [])
        busy.append(busy_union_ns([(s, s + d) for _n, s, d in ops]) / 1e9)
        for name, _s, d in ops:
            tot = out["ops"].setdefault(name, [0.0, 0])
            tot[0] += d / 1e9
            tot[1] += 1
        for name, s, d in lines.get(modules_line, []):
            if s <= first + EDGE_NS or s + d >= last - EDGE_NS:
                continue  # cut by the trace's start or end: not a whole run
            tot = out["programs"].setdefault(name, [0.0, 0])
            tot[0] += d / 1e9
            tot[1] += 1
        if not out["gaps"]:
            out["gaps"] = gaps([(s, s + d, n) for n, s, d in ops],
                               (first, last))
    if busy:
        out["busy_s"] = sum(busy) / len(busy)
    return out


def _reduced(ctx: dict, device_plane, ops_line, modules_line) -> dict | None:
    if not ctx.get("profile_dir"):
        return None
    path = find_trace(ctx["profile_dir"])
    if path is None:
        return None
    return reduce(load(path, device_plane), ops_line, modules_line)


def program_seconds(red: dict, programs: list[str]) -> tuple[float, int]:
    """Summed device seconds and executions of the programs named so: the
    trace calls a program `<name>(<fingerprint>)`."""
    total, count = 0.0, 0
    for name, (seconds, n) in red["programs"].items():
        if name.split("(", 1)[0] in programs:
            total += seconds
            count += n
    return total, count


def read(ctx: dict, programs: list[str] | None = None,
         idle_share: bool = False,
         device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE,
         modules_line: str = MODULES_LINE) -> float | None:
    red = _reduced(ctx, device_plane, ops_line, modules_line)
    if red is None or red["busy_s"] is None:
        return None
    if idle_share:
        if red["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    total, count = program_seconds(red, programs or [])
    return 1e3 * total / count if count else None


def short_name(op: str) -> str:
    """`%fusion.264 = u32[267584]{...} fusion(...)` -> `fusion.264`: the
    trace names an operation by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%")[:80]


def device_summary(ctx: dict) -> dict:
    """What the result's line carries beside the metrics: busy_s and window_s
    under `device`, and the breakdown."""
    red = _reduced(ctx, DEVICE_PLANE, OPS_LINE, MODULES_LINE)
    if red is None or red["busy_s"] is None:
        return {}
    top = sorted(((short_name(n), s) for n, (s, _c) in red["ops"].items()),
                 key=lambda t: -t[1])[:10]
    # what the host did in a gap needs host annotations on the profiler's
    # clock, which the program lacks: a gap is named by what ran before it
    idle = [[f"after {short_name(name)}", seconds / 1e9]
            for seconds, name in red["gaps"][:10]]
    return {"device": {"busy_s": red["busy_s"], "window_s": red["window_s"]},
            "breakdown": {"device_ops": [[n, s] for n, s in top],
                          "idle_gaps": idle},
            "programs": {n: {"seconds": s, "count": c}
                         for n, (s, c) in red["programs"].items()}}
