"""The benchmark's entry for the core server process, the one that holds the
chip. It runs `foundationdb_tpu.net.server_main.main(spec)` unchanged; beside
it a thread answers run.py's words on stdin, because only this process can
trace its chip or read its memory:

    {"cmd": "trace_start", "dir": ...}   open jax.profiler's trace
    {"cmd": "trace_stop"}                close it
    {"cmd": "memory"}                    peak bytes in use on the fullest chip,
                                         and this process's full collections

Each answer is one JSON line on stdout, after server_main's `ready` line.
Without `--spans` the program's span records, which it would print to stderr,
are dropped: the untraced run writes none.

    python benchmark/core_main.py '<spec json>' [--spans]
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import time


class GcWatch:
    """How long this process's full garbage collections held it (a commit
    path that stands still for 2 s breaks its clients' connections)."""

    def __init__(self):
        self.began, self.longest, self.total, self.count = 0.0, 0.0, 0.0, 0
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self.began = time.monotonic()
        else:
            took = time.monotonic() - self.began
            self.count += 1
            self.total += took
            self.longest = max(self.longest, took)


def _answer(**fields) -> None:
    sys.stdout.write(json.dumps(fields) + "\n")
    sys.stdout.flush()


def _control(gc_watch: GcWatch) -> None:
    for line in sys.stdin:
        try:
            word = json.loads(line)
            cmd = word["cmd"]
            if cmd == "trace_start":
                import jax
                # the device's planes and the runtime's own host events; the
                # Python tracer's record of every call of the server made
                # stop_trace hold this process for 30 s (chip run, PR 25)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(word["dir"],
                                         profiler_options=options)
                _answer(ok=True, cmd=cmd)
            elif cmd == "trace_stop":
                import jax
                jax.profiler.stop_trace()
                _answer(ok=True, cmd=cmd)
            elif cmd == "memory":
                import jax
                peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                         for d in jax.devices()]
                known = [p for p in peaks if p is not None]
                _answer(ok=True, cmd=cmd,
                        memory_peak_bytes=max(known) if known else None,
                        gc_full_collections=gc_watch.count,
                        gc_full_seconds=round(gc_watch.total, 4),
                        gc_full_longest_seconds=round(gc_watch.longest, 4))
            else:
                _answer(ok=False, cmd=cmd, error="unknown command")
        except Exception as e:  # noqa: BLE001 — the server must keep serving
            _answer(ok=False, error=f"{type(e).__name__}: {e}")


def drop_spans(record: dict) -> None:
    """A sink that keeps what the program has to say (events, counters) on
    stderr and drops the per-batch span, attach and probe records."""
    if "Span" in record or "To" in record or "Location" in record:
        return
    sys.stderr.write(json.dumps(record, default=str) + "\n")


def main(argv: list[str]) -> None:
    spec_json, spans = argv[1], "--spans" in argv[2:]
    from foundationdb_tpu.net import server_main
    if not spans:
        from foundationdb_tpu.utils import trace
        trace.set_sink(drop_spans)
    threading.Thread(target=_control, args=(GcWatch(),),
                     name="bench-control", daemon=True).start()
    server_main.main(spec_json)


if __name__ == "__main__":
    main(sys.argv)
