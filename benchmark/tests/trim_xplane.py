"""How tests/data/fdb-write.trimmed.xplane.pb was made: a trace recorded on
the chip (PR 25, `fdb-write`, TPU v5 lite) cut to its first `--seconds`, the
events' stats dropped, the device's plane and the host's kept. Works on the
protobuf wire format directly, since no XSpace classes are installed here.

    python benchmark/tests/trim_xplane.py <in.xplane.pb> <out.xplane.pb> \
        --seconds 0.25
"""

import argparse

# XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4 (map: key=1,
# value=2{id=1,name=2}); XLine: timestamp_ns=3 events=4;
# XEvent: metadata_id=1 offset_ps=2 duration_ps=3 stats=4


def varint(buf: bytes, at: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, at
        shift += 7


def fields(buf: bytes):
    """(field number, wire type, value, the field's raw bytes)."""
    at = 0
    while at < len(buf):
        start = at
        key, at = varint(buf, at)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, at = varint(buf, at)
        elif wt == 2:
            n, at = varint(buf, at)
            val, at = buf[at:at + n], at + n
        elif wt == 1:
            val, at = buf[at:at + 8], at + 8
        elif wt == 5:
            val, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val, buf[start:at]


def enc_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def delimited(num: int, payload: bytes) -> bytes:
    return enc_varint(num << 3 | 2) + enc_varint(len(payload)) + payload


def trim_plane(plane: bytes, until_ns: float) -> bytes:
    used, lines, rest, metadata = set(), [], [], []
    for num, _wt, val, raw in fields(plane):
        if num == 3:
            t_line, events, head = 0, [], []
            for n2, _w2, v2, raw2 in fields(val):
                if n2 == 3:
                    t_line = v2
                if n2 == 4:
                    events.append(v2)
                else:
                    head.append(raw2)
            kept = []
            for ev in events:
                f = {n3: v3 for n3, _w3, v3, _r3 in fields(ev)}
                if t_line + f.get(2, 0) / 1e3 > until_ns:
                    continue
                used.add(f.get(1, 0))
                kept.append(delimited(4, b"".join(
                    r3 for n3, _w3, _v3, r3 in fields(ev) if n3 != 4)))
            if kept:
                lines.append(delimited(3, b"".join(head + kept)))
        elif num == 4:
            metadata.append((val, raw))
        elif num != 6:
            rest.append(raw)
    for val, raw in metadata:
        key = next(v for n, _w, v, _r in fields(val) if n == 1)
        if key in used:
            rest.append(raw)
    return b"".join(rest + lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--seconds", type=float, default=0.25)
    ap.add_argument("--planes", nargs="+",
                    default=["/device:TPU:0", "/host:CPU"])
    args = ap.parse_args()
    with open(args.src, "rb") as f:
        space = f.read()
    planes, first = [], None
    for num, _wt, val, _raw in fields(space):
        if num != 1:
            continue
        name = next(v for n, _w, v, _r in fields(val) if n == 2).decode()
        if name not in args.planes:
            continue
        planes.append(val)
        for n, _w, v, _r in fields(val):
            if n == 3:
                t = next((v2 for n2, _w2, v2, _r2 in fields(v) if n2 == 3), 0)
                if any(n2 == 4 for n2, *_ in fields(v)):
                    first = t if first is None else min(first, t)
    until = first + args.seconds * 1e9
    with open(args.dst, "wb") as f:
        f.write(b"".join(delimited(1, trim_plane(p, until)) for p in planes))


if __name__ == "__main__":
    main()
