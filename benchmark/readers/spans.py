"""The program's span files (`FDBTPU_TRACE_DIR`, JSON lines of Begin/End
records on time.monotonic) reduced to durations. The pairing is a copy of
`foundationdb_tpu/tools/trace_analyze.pair_spans`: Begin and End match by
(ID, Span), first in first out."""

import glob
import json
import os


def load_durations(span_dir: str, span: str, window: tuple) -> list[float]:
    """Seconds of every `span` that ended inside the window."""
    t_open, t_close = window
    out = []
    for path in sorted(glob.glob(os.path.join(span_dir, "trace.*"))):
        open_spans: dict[str, list[float]] = {}
        with open(path) as f:
            for line in f:
                if span not in line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # a process stopped mid-line
                if rec.get("Span") != span:
                    continue
                ident = str(rec.get("ID"))
                if rec.get("Phase") == "Begin":
                    open_spans.setdefault(ident, []).append(rec["Time"])
                elif rec.get("Phase") == "End" and open_spans.get(ident):
                    begin = open_spans[ident].pop(0)
                    if t_open <= rec["Time"] <= t_close:
                        out.append(rec["Time"] - begin)
    return out


def read(ctx: dict, span: str, q: float, scale: float = 1e3) -> float | None:
    """The q-quantile (nearest rank) of the span's durations, in ms."""
    import math
    span_dir = os.path.join(ctx["run_dir"], "spans")
    durations = sorted(load_durations(span_dir, span, ctx["window"]))
    if not durations:
        return None
    return scale * durations[max(0, math.ceil(q * len(durations)) - 1)]
