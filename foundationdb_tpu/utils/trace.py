"""Structured trace events + counters.

Reference: flow/Trace.cpp (`TraceEvent("Type", id).detail(k, v)` structured
logging with severities and rolling files) and flow/Stats.h (Counter /
CounterCollection periodically dumped into the trace log).

We log JSON lines. The global sink is swappable so the simulator can timestamp
events with virtual time and tests can capture them. Span, attach and probe
records (TraceBatch) exist only while a sink is installed; TraceEvents and
counter dumps go to stderr without one.
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from typing import Callable

SevDebug, SevInfo, SevWarn, SevWarnAlways, SevError = 5, 10, 20, 30, 40

_now: Callable[[], float] = time.time
_sink: Callable[[dict], None] | None = None
_min_severity = SevInfo
_annotator = None


def set_clock(fn: Callable[[], float]):
    global _now
    _now = fn


def set_sink(fn: Callable[[dict], None] | None):
    """Install the record sink. It is also the switch of the process-wide
    span buffer: with no sink nobody listens, and g_trace_batch builds no
    record at all."""
    global _sink
    _sink = fn
    g_trace_batch.enabled = fn is not None


def set_annotator(fn):
    """Hook for sections: `fn(span, ident, mono_us)` returns a context
    manager the section's body runs inside. ops/conflict.py installs one
    that opens a jax.profiler.TraceAnnotation, which puts the section on the
    profiler's clock; this module never imports JAX."""
    global _annotator
    _annotator = fn


def set_min_severity(sev: int):
    global _min_severity
    _min_severity = sev


class TraceEvent:
    __slots__ = ("_fields", "_sev")

    def __init__(self, event_type: str, ident=None, severity: int = SevInfo):
        self._sev = severity
        self._fields = {"Type": event_type, "Time": round(_now(), 6)}
        if ident is not None:
            self._fields["ID"] = str(ident)

    def detail(self, key: str, value) -> "TraceEvent":
        self._fields[key] = value
        return self

    def error(self, e: BaseException) -> "TraceEvent":
        self._sev = max(self._sev, SevError)
        self._fields["Error"] = repr(e)
        return self

    def log(self):
        if self._sev < _min_severity:
            return
        if (_suppression is not None and self._sev < SevError
                and not _suppression.admit(self._fields)):
            return  # rate-suppressed (errors always pass)
        if _sink is not None:
            _sink(self._fields)
        else:
            print(json.dumps(self._fields, default=str), file=sys.stderr)


def __getattr__(name):
    # Counter/CounterCollection/trace_counters_loop live in utils/stats.py
    # (the canonical flow/Stats.h port); re-exported lazily because stats
    # imports TraceEvent from this module.
    if name in ("Counter", "CounterCollection", "trace_counters_loop"):
        from foundationdb_tpu.utils import stats
        return getattr(stats, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class RollingTraceFile:
    """Rolling trace sink (flow/Trace.h:260 openTraceFile): JSON lines into
    `path`, rolled to `path.<n>` when `roll_bytes` is exceeded, keeping the
    newest `keep` rolls. Install with set_sink(rt.write).

    An event or a counter dump reaches the file at once. Span, attach and
    probe records ride the file's buffer: they arrive thousands at a time
    (TraceBatch.dump, on the loop thread), and a system call a record held
    the core's loop for 120–170 ms a flush (chip run, PR 26)."""

    def __init__(self, path: str, roll_bytes: int = 10_000_000, keep: int = 10):
        import os
        self.path = path
        self.roll_bytes = roll_bytes
        self.keep = keep
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = self._open()
        # sections on a blocking-pool thread may flush the span buffer while
        # the loop thread logs an event
        self._lock = threading.Lock()

    def _open(self):
        # binary, so that tell() counts the buffered bytes without a flush
        return open(self.path, "ab", buffering=1 << 16)

    def write(self, fields: dict):
        line = (json.dumps(fields, default=str) + "\n").encode()
        with self._lock:
            self._f.write(line)
            if not ("Span" in fields or "To" in fields
                    or "Location" in fields):
                self._f.flush()
            if self._f.tell() >= self.roll_bytes:
                self.roll()

    def roll(self):
        import os
        self._f.close()
        for i in range(self.keep - 1, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        os.replace(self.path, f"{self.path}.1")
        self._f = self._open()

    def close(self):
        self._f.close()


class _Suppression:
    """Per-type rate suppression (Trace.cpp's suppressFor): at most `limit`
    events of one Type per `interval` seconds; excess is counted and
    surfaced once per interval as a Suppressed event."""

    def __init__(self, limit: int = 100, interval: float = 5.0):
        self.limit = limit
        self.interval = interval
        self._windows: dict[str, tuple[float, int, int]] = {}

    def admit(self, fields: dict) -> bool:
        ty = fields.get("Type", "")
        now = fields.get("Time", 0.0)
        start, n, dropped = self._windows.get(ty, (now, 0, 0))
        if now - start >= self.interval:
            if dropped:
                emit = {"Type": "TraceEventsSuppressed", "Time": now,
                        "OfType": ty, "Dropped": dropped}
                if _sink is not None:
                    _sink(emit)
                else:
                    print(json.dumps(emit), file=sys.stderr)
            start, n, dropped = now, 0, 0
        if n >= self.limit:
            self._windows[ty] = (start, n, dropped + 1)
            return False
        self._windows[ty] = (start, n + 1, dropped)
        return True


_suppression: _Suppression | None = None


def enable_suppression(limit: int = 100, interval: float = 5.0):
    global _suppression
    _suppression = _Suppression(limit, interval)


def flush_suppressed():
    """Emit pending Dropped counts (a chatty type that went quiet would
    otherwise never surface its final window's suppression)."""
    if _suppression is None:
        return
    for ty, (start, _n, dropped) in list(_suppression._windows.items()):
        if dropped:
            emit = {"Type": "TraceEventsSuppressed", "Time": _now(),
                    "OfType": ty, "Dropped": dropped}
            if _sink is not None:
                _sink(emit)
            else:
                print(json.dumps(emit), file=sys.stderr)
    _suppression._windows.clear()


def disable_suppression():
    global _suppression
    flush_suppressed()
    _suppression = None


# what a section costs when nobody listens (reusable, holds nothing)
_NULL_SECTION = contextlib.nullcontext()


class _Section:
    """One synchronous section on one thread: Begin/End records, and the
    annotator's context round the body."""

    __slots__ = ("_batch", "_kind", "_ident", "_span", "_now", "_annotation")

    def __init__(self, batch, kind, ident, span, now):
        self._batch, self._kind, self._ident = batch, kind, ident
        self._span, self._now = span, now
        self._annotation = None

    def __enter__(self):
        self._batch._span(self._kind, self._ident, self._span, "Begin",
                          self._now())
        self._annotation = self._batch.annotate(self._span, self._ident)
        self._annotation.__enter__()

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        # written whatever the body did: every Begin gets an End
        self._batch._span(self._kind, self._ident, self._span, "End",
                          self._now())
        return False


class TraceBatch:
    """g_traceBatch (flow/Trace.h): micro-timing attach/event records that
    stitch ONE transaction's timeline across processes — the commit path
    emits `addEvent("CommitDebug", id, "Proxy.commitBatch.Before")`-style
    probes (NativeAPI.actor.cpp:2689, MasterProxyServer.actor.cpp:356,
    Resolver.actor.cpp:83). Buffered; dump() flushes to the sink.

    `enabled` is the off switch: while it is False every recording method
    returns after testing it. The process-wide g_trace_batch follows the
    sink (set_sink); a batch built by hand records from the start."""

    def __init__(self, max_buffer: int = 4096, enabled: bool = True):
        self.max_buffer = max_buffer
        self.enabled = enabled
        self._events: list[dict] = []

    def add_event(self, kind: str, ident, location: str, at: float | None = None):
        if not self.enabled:
            return
        self._events.append({"Type": kind,
                             "Time": round(_now() if at is None else at, 6),
                             "ID": str(ident), "Location": location})
        if len(self._events) >= self.max_buffer:
            self.dump()

    def add_attach(self, kind: str, ident, to: str, at: float | None = None):
        """Link two ids (e.g. a transaction to its commit batch)."""
        if not self.enabled:
            return
        self._events.append({"Type": kind,
                             "Time": round(_now() if at is None else at, 6),
                             "ID": str(ident), "To": str(to)})
        if len(self._events) >= self.max_buffer:
            self.dump()

    def span_begin(self, kind: str, ident, span: str, at: float | None = None):
        """Begin a named stage span for one id. Pass `at=loop.now()` so sim
        roles stamp virtual time (the global clock is per-interpreter and a
        process never owns it)."""
        if not self.enabled:
            return
        self._span(kind, ident, span, "Begin", at)

    def span_end(self, kind: str, ident, span: str, at: float | None = None):
        if not self.enabled:
            return
        self._span(kind, ident, span, "End", at)

    def section(self, kind: str, ident, span: str,
                now: Callable[[], float] = time.monotonic):
        """Context manager for a synchronous section on the calling thread:
        the same Begin/End pair as span_begin/span_end, stamped by `now`
        (pass `loop.now`, so that the simulator stamps virtual time), and
        the body inside the annotator's context when one is installed. The
        End is written even when the body raises."""
        if not self.enabled:
            return _NULL_SECTION
        return _Section(self, kind, ident, span, now)

    def annotate(self, span: str, ident):
        """The annotator's context alone, for a section whose records are
        written elsewhere. `mono_us` is time.monotonic at entry, which makes
        every annotation one reading of both clocks."""
        if not self.enabled or _annotator is None:
            return _NULL_SECTION
        return _annotator(span, str(ident), int(time.monotonic() * 1e6))

    def _span(self, kind: str, ident, span: str, phase: str, at: float | None):
        self._events.append({"Type": kind,
                             "Time": round(_now() if at is None else at, 6),
                             "ID": str(ident), "Span": span, "Phase": phase})
        if len(self._events) >= self.max_buffer:
            self.dump()

    def dump(self):
        """Hand the buffered records to the sink; with none they are dropped
        (nothing of a span is ever printed to stderr)."""
        events, self._events = self._events, []
        if _sink is not None:
            for e in events:
                _sink(e)

    def timeline(self, ident) -> list[dict]:
        """Buffered records for one id (tests/debugging)."""
        return [e for e in self._events if e.get("ID") == str(ident)]


# flushed a thousand records at a time: a flush runs on the loop thread, and
# 4096 records of JSON held it for 20 ms with the file's writes buffered
g_trace_batch = TraceBatch(max_buffer=1024, enabled=False)


def span_full_collections():
    """Install the process's one watcher of full (generation 2) garbage
    collections. A full collection holds the GIL, so for its length no
    thread of the process runs Python — the loop does not tick and a
    blocking thread cannot return. Each one adds to the process counters
    `FullCollections` and `FullCollectionSeconds` (utils/stats) and, while
    spans are recorded, writes a `Loop.FullGC` span pair. Returns the
    callback (gc.callbacks), so that a test can take it out again."""
    import gc

    from foundationdb_tpu.utils import stats  # it imports this module
    began = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            began[0] = time.monotonic()
            return
        ended = time.monotonic()
        stats.full_collections.increment()
        stats.full_collection_seconds.increment(ended - began[0])
        if g_trace_batch.enabled:
            ident = f"gc{gc.get_stats()[2]['collections']}"
            g_trace_batch.span_begin("LoopSpan", ident, "Loop.FullGC",
                                     at=began[0])
            g_trace_batch.span_end("LoopSpan", ident, "Loop.FullGC",
                                   at=ended)

    gc.callbacks.append(on_gc)
    return on_gc


class LatencyBands:
    """Latency histogram traced alongside counters (the reference's
    LatencyBands in Stats.h / proxy GRV+commit bands): fixed upper-bound
    bands in seconds, counts per band."""

    BANDS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0)

    def __init__(self, name: str):
        self.name = name
        self.counts = [0] * (len(self.BANDS) + 1)
        self.total = 0
        self.max_seen = 0.0

    def add(self, seconds: float):
        from bisect import bisect_left
        self.counts[bisect_left(self.BANDS, seconds)] += 1
        self.total += 1
        self.max_seen = max(self.max_seen, seconds)

    def trace(self):
        ev = TraceEvent(f"{self.name}LatencyBands")
        for bound, n in zip(self.BANDS, self.counts):
            if n:
                ev.detail(f"le_{bound}", n)
        if self.counts[-1]:
            ev.detail("gt_last", self.counts[-1])
        ev.detail("Total", self.total).detail("Max", round(self.max_seen, 6))
        ev.log()
