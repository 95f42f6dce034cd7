"""Why the device was idle: every idle stretch of the traced window named by
what the core process was doing in it, from the program's own spans.

The device's operations are on the profiler's clock and the program's spans
(`FDBTPU_TRACE_DIR`, JSON lines) on time.monotonic. The program's sections
(`utils/trace.py` `TraceBatch.section`) tie the two: each is also a
`TraceAnnotation` on the host's plane carrying `mono_us`, time.monotonic at
its entry, so each is one reading of both clocks and their offset is the
median over the trace's annotations. A program without such annotations
cannot be read: every metric here is then None.

An idle instant has the first cause that holds, in this order:

- `dispatch`: a `Resolver.Dispatch` section is open (the chip waits for the
  host's encode and enqueue);
- `pending`: some batch's `Proxy.Resolve` has begun and its
  `Resolver.Dispatch` has not (RPC, version gate, the loop's queue);
- `assembling`: a `Proxy.BatchAssembly`, `Proxy.QueueDelay` or
  `Proxy.GetCommitVersion` span is open (the proxy holds transactions);
- `no_work`: none of these.

Each is a share of the traced window, so the four add up to the idle share
(`xplane.read(idle_share=True)` over the same trace).

The device's plane and the host's are not stamped alike: in this chip's
traces a program's first operation lies 1.9–2.0 ms *before* the host's
`DoEnqueueProgram` of the same `run_id` (PR 26: 10 of 10 launches onto an
idle device; `tpu::System::Execute=>Done` follows the last operation by 2.4
ms). The device cannot start what the host has not launched, so the largest
such lead over the trace's launches is how early the device's plane is
stamped, and the spans are moved earlier by that much before they meet the
device's intervals. Without it the end of every gap, the 2 ms in which the
dispatch is still open, would be read as busy.

Loading is kept apart from the reduction, which works on plain intervals.
"""

import glob
import json
import os
import statistics

from readers import xplane

HOST_PLANE = "/host:CPU"
MONO_STAT = "mono_us"
LAUNCH_EVENT = "DoEnqueueProgram"
RUN_STAT = "run_id"
CAUSES = ("dispatch", "pending", "assembling", "no_work")
DISPATCH = "Resolver.Dispatch"
RESOLVE = "Proxy.Resolve"
ASSEMBLING = ("Proxy.BatchAssembly", "Proxy.QueueDelay",
              "Proxy.GetCommitVersion")

_cache: dict = {}


# ------------------------------------------------- intervals (pure)

def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same instants."""
    out: list[list[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def intersect(a: list, b: list) -> list[tuple[float, float]]:
    """Instants in both of two sorted, disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if end > start:
            out.append((start, end))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list[tuple[float, float]]:
    """Instants of sorted, disjoint `a` not in sorted, disjoint `b`."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > start:
                out.append((start, b[k][0]))
            start = max(start, b[k][1])
            k += 1
        if end > start:
            out.append((start, end))
    return out


def length(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def idle_intervals(busy: list, window: tuple[float, float]) -> list:
    return subtract([window], union(busy))


def attribute(idle: list, dispatch: list, pending: list,
              assembling: list) -> dict:
    """{cause: [intervals]}: the idle intervals cut at the causes' edges,
    each piece under the first cause that covers it."""
    out, rest = {}, union(idle)
    for cause, spans in (("dispatch", dispatch), ("pending", pending),
                         ("assembling", assembling)):
        spans = union(spans)
        out[cause] = intersect(rest, spans)
        rest = subtract(rest, spans)
    out["no_work"] = rest
    return out


def clock_offset_ns(readings: list[tuple[float, float]]) -> float | None:
    """Profiler ns minus time.monotonic ns, from (profiler start ns,
    mono_us) pairs: the median, since a reading taken a moment before its
    annotation opened errs to one side by that moment only."""
    if not readings:
        return None
    return statistics.median(ns - us * 1e3 for ns, us in readings)


def device_lead_ns(launches: list[tuple[float, float]]) -> float:
    """How early the device's plane is stamped against the host's, from
    (device start ns, host launch ns) of the same runs: a program starts
    after its launch, so the largest lead of the device over the host is
    clock, not cause. A run that queued behind another started long after its
    launch and says nothing: where every run queued (a device that is never
    idle), or none is in the trace, the lead reads 0."""
    return max([0.0] + [host - device for device, host in launches])


def pending_intervals(spans: dict, attach: dict) -> list:
    """[Proxy.Resolve's begin, the begin of the same batch's
    Resolver.Dispatch], by the batch's attach to its commit version; a
    batch whose dispatch is not in the files is pending until its resolve
    ends."""
    dispatched = {ident: begin for ident, begin, _e in spans.get(DISPATCH, [])}
    out = []
    for ident, begin, end in spans.get(RESOLVE, []):
        at = min((dispatched[v] for v in attach.get(ident, ())
                  if v in dispatched), default=end)
        out.append((begin, min(max(at, begin), end)))
    return out


# ------------------------------------------------------------ loading

def load_timeline(path: str, device_plane: str = xplane.DEVICE_PLANE,
                  modules_line: str = xplane.MODULES_LINE,
                  host_plane: str = HOST_PLANE, mono_stat: str = MONO_STAT,
                  launch_event: str = LAUNCH_EVENT,
                  run_stat: str = RUN_STAT) -> dict:
    """One pass over the trace for what ties the clocks:
    {"notes": [(name, start ns, duration ns, mono_us)] of every event of the
    host's plane that carries `mono_stat`; "launches": [(device start ns,
    host launch ns)] of every program execution whose `run_stat` is on both
    planes}."""
    key = (path, device_plane, modules_line, host_plane, mono_stat,
           launch_event, run_stat)
    if key in _cache:
        return _cache[key]
    from jax.profiler import ProfileData
    notes, device, host = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(device_plane)
        if not on_device and plane.name != host_plane:
            continue
        for line in plane.lines:
            if on_device and line.name != modules_line:
                continue
            for e in line.events:
                stats = dict(e.stats)
                if on_device:
                    if run_stat in stats:
                        device[str(stats[run_stat])] = float(e.start_ns)
                elif mono_stat in stats:
                    notes.append((e.name, float(e.start_ns),
                                  float(e.duration_ns),
                                  float(stats[mono_stat])))
                elif e.name == launch_event and run_stat in stats:
                    host[str(stats[run_stat])] = float(e.start_ns)
    _cache[key] = {"notes": notes, "launches": [
        (device[r], host[r]) for r in device if r in host]}
    return _cache[key]


def load_spans(span_dir: str) -> tuple[dict, dict]:
    """({span: [(ident, begin, end)]}, {ident: [attached to]}) of every file:
    Begin and End match by (ID, Span), first in first out, as in spans.py."""
    if span_dir in _cache:
        return _cache[span_dir]
    spans: dict = {}
    attach: dict = {}
    for path in sorted(glob.glob(os.path.join(span_dir, "trace.*"))):
        open_spans: dict = {}
        with open(path) as f:
            for line in f:
                if '"Span"' not in line and '"To"' not in line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # a process stopped mid-line
                ident = str(rec.get("ID"))
                if "To" in rec:
                    attach.setdefault(ident, []).append(str(rec["To"]))
                    continue
                key = (ident, rec.get("Span"))
                if rec.get("Phase") == "Begin":
                    open_spans.setdefault(key, []).append(rec["Time"])
                elif rec.get("Phase") == "End" and open_spans.get(key):
                    spans.setdefault(rec["Span"], []).append(
                        (ident, open_spans[key].pop(0), rec["Time"]))
    _cache[span_dir] = (spans, attach)
    return spans, attach


def causes(ctx: dict, device_plane: str = xplane.DEVICE_PLANE,
           ops_line: str = xplane.OPS_LINE,
           modules_line: str = xplane.MODULES_LINE,
           host_plane: str = HOST_PLANE, mono_stat: str = MONO_STAT,
           launch_event: str = LAUNCH_EVENT,
           run_stat: str = RUN_STAT) -> dict | None:
    """{"window_s", "idle_s", "offset_ns", "readings", "device_lead_ns",
    cause: seconds, and
    "gaps": the ten longest idle stretches with the spans open in each}; None
    where the profile, the annotations or the span files are missing."""
    if not ctx.get("profile_dir"):
        return None
    path = xplane.find_trace(ctx["profile_dir"])
    span_dir = os.path.join(ctx["run_dir"], "spans")
    if path is None or not os.path.isdir(span_dir):
        return None
    tie = load_timeline(path, device_plane, modules_line, host_plane,
                        mono_stat, launch_event, run_stat)
    notes = tie["notes"]
    offset = clock_offset_ns([(ns, us) for _n, ns, _d, us in notes])
    if offset is None:
        return None
    trace = xplane.load(path, device_plane)
    first, last = trace["span_ns"]
    if first is None or last <= first:
        return None
    spans, attach = load_spans(span_dir)

    lead = device_lead_ns(tie["launches"])
    shift = offset - lead  # time.monotonic ns -> the device plane's ns

    def on_device_clock(intervals: list) -> list:
        return [(b * 1e9 + shift, e * 1e9 + shift) for b, e in intervals]

    by_cause = {
        "dispatch": [(b, e) for _i, b, e in spans.get(DISPATCH, [])],
        "pending": pending_intervals(spans, attach),
        "assembling": [(b, e) for name in ASSEMBLING
                       for _i, b, e in spans.get(name, [])]}
    # one device: this benchmark's cells hold one chip's worth of planes
    # each; with several the idle time is that of the first
    lines = next(iter(sorted(trace["devices"].items())), (None, {}))[1]
    busy = [(s, s + d) for _n, s, d in lines.get(ops_line, [])]
    idle = idle_intervals(busy, (first, last))
    parts = attribute(idle, *(on_device_clock(by_cause[c])
                              for c in CAUSES[:3]))
    out = {"window_s": (last - first) / 1e9, "idle_s": length(idle) / 1e9,
           "offset_ns": offset, "readings": len(notes),
           "device_lead_ns": lead}
    for cause in CAUSES:
        out[cause] = length(parts[cause]) / 1e9
    # the longest gaps, each with the core's spans that were open in it (ms
    # from the gap's start) and a count of the clients'
    named = [(name, ident, b * 1e9 + shift, e * 1e9 + shift)
             for name, rows in spans.items() for ident, b, e in rows]
    out["gaps"] = []
    for start, end in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        inside = sorted((max(b, start), name, ident, min(e, end))
                        for name, ident, b, e in named
                        if b < end and e > start)
        clients: dict = {}
        for _b, name, _i, _e in inside:
            if name.startswith("Client."):
                clients[name] = clients.get(name, 0) + 1
        out["gaps"].append({
            "at_s": (start - first) / 1e9, "seconds": (end - start) / 1e9,
            "by_cause": {c: length(intersect([(start, end)], parts[c])) / 1e9
                         for c in CAUSES},
            "clients": clients,
            "open": [[name, ident, round((b - start) / 1e6, 3),
                      round((e - start) / 1e6, 3)]
                     for b, name, ident, e in inside
                     if not name.startswith("Client.")]})
    return out


def read(ctx: dict, cause: str, **names) -> float | None:
    """The share (%) of the traced window in which the device was idle for
    `cause`; `names` as for `causes`."""
    got = causes(ctx, **names)
    if got is None or got["window_s"] <= 0:
        return None
    return 100.0 * got[cause] / got["window_s"]
