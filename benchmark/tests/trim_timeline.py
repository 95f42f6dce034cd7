"""How tests/data/fdb-write.pr26.trimmed.* were made: a traced `fdb-write` run
on the chip (PR 26, TPU v5 lite) cut to `--seconds` of its trace from
`--from`, with what the readers of PR 26 need and trim_xplane.py drops:

- the trace keeps the events' stats (`id`, `mono_us` of the program's
  annotations), of the device's plane only the operations' and the programs'
  lines, and the host's plane;
- the span files become one file holding the core's span and attach records
  of the same stretch (through the clock offset the trace's annotations
  give) and a second either side; the scope maps are kept whole.

    python benchmark/tests/trim_timeline.py <in.xplane.pb> <span dir> \
        <out.xplane.pb> <out.spans.tgz> --from 1.0 --seconds 0.3
"""

import argparse
import glob
import io
import json
import os
import sys
import tarfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from trim_xplane import delimited, fields  # noqa: E402

DEVICE_LINES = ("XLA Ops", "XLA Modules")


def trim_plane(plane: bytes, since_ns: float, until_ns: float,
               only_lines: tuple | None) -> bytes:
    """The plane with the events that lie whole inside [since, until],
    stats and all, and the event metadata those events use."""
    used, lines, rest, metadata = set(), [], [], []
    for num, _wt, val, raw in fields(plane):
        if num == 3:
            t_line, name, events, head = 0, "", [], []
            for n2, _w2, v2, raw2 in fields(val):
                if n2 == 3:
                    t_line = v2
                if n2 == 2:
                    name = v2.decode()
                if n2 == 4:
                    events.append((v2, raw2))
                else:
                    head.append(raw2)
            if only_lines is not None and name not in only_lines:
                continue
            kept = []
            for ev, raw_ev in events:
                f = {n3: v3 for n3, _w3, v3, _r3 in fields(ev)}
                start = t_line + f.get(2, 0) / 1e3
                if since_ns <= start and start + f.get(3, 0) / 1e3 <= until_ns:
                    used.add(f.get(1, 0))
                    kept.append(raw_ev)
            if kept:
                lines.append(delimited(3, b"".join(head + kept)))
        elif num == 4:
            metadata.append((val, raw))
        else:
            rest.append(raw)
    for val, raw in metadata:
        key = next(v for n, _w, v, _r in fields(val) if n == 1)
        if key in used:
            rest.append(raw)
    return b"".join(rest + lines)


def first_event_ns(planes: list[bytes]) -> float:
    first = None
    for plane in planes:
        for n, _w, v, _r in fields(plane):
            if n != 3:
                continue
            t = next((v2 for n2, _w2, v2, _r2 in fields(v) if n2 == 3), 0)
            for n2, _w2, v2, _r2 in fields(v):
                if n2 == 4:
                    off = next((v3 for n3, _w3, v3, _r3 in fields(v2)
                                if n3 == 2), 0)
                    at = t + off / 1e3
                    first = at if first is None else min(first, at)
    return first


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("span_dir")
    ap.add_argument("dst")
    ap.add_argument("dst_spans")
    ap.add_argument("--from", dest="since", type=float, default=1.0)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--planes", nargs="+",
                    default=["/device:TPU:0", "/host:CPU"])
    args = ap.parse_args()
    with open(args.src, "rb") as f:
        space = f.read()
    planes = {}
    for num, _wt, val, _raw in fields(space):
        if num == 1:
            name = next(v for n, _w, v, _r in fields(val) if n == 2).decode()
            if name in args.planes:
                planes[name] = val
    since = first_event_ns(list(planes.values())) + args.since * 1e9
    until = since + args.seconds * 1e9
    with open(args.dst, "wb") as f:
        f.write(b"".join(delimited(1, trim_plane(
            p, since, until,
            DEVICE_LINES if name.startswith("/device") else None))
            for name, p in planes.items()))

    # the spans of the same stretch, on time.monotonic
    from readers import gap_cause
    notes = gap_cause.load_timeline(args.dst)["notes"]
    offset = gap_cause.clock_offset_ns([(ns, us) for _n, ns, _d, us in notes])
    lo, hi = (since - offset) / 1e9 - 1.0, (until - offset) / 1e9 + 1.0
    kept = []
    for path in sorted(glob.glob(os.path.join(args.span_dir, "trace.*"))):
        with open(path) as f:
            for line in f:
                if '"Span"' not in line and '"To"' not in line:
                    continue
                rec = json.loads(line)
                if not lo <= rec["Time"] <= hi:
                    continue
                if str(rec.get("Span", "")).startswith("Client.") or (
                        "To" in rec and not str(rec["ID"]).startswith("b")):
                    continue  # the clients' own, and their attaches
                kept.append((rec["Time"], line))
    kept.sort(key=lambda t: t[0])
    with tarfile.open(args.dst_spans, "w:gz") as tar:
        def add(name: str, data: bytes):
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
        add("spans/trace.core.jsonl",
            "".join(line for _t, line in kept).encode())
        for path in sorted(glob.glob(os.path.join(args.span_dir,
                                                  "scopes.*.json"))):
            with open(path, "rb") as f:
                add("spans/" + os.path.basename(path), f.read())
    print(f"{len(notes)} annotations, offset {offset:.0f} ns, "
          f"{len(kept)} span records")


if __name__ == "__main__":
    main()
