"""Sampling profiler: where does the host loop spend its time?

Reference: flow/Profiler.actor.cpp — a SIGPROF-driven sampler that records
the running stack at a fixed interval into the trace stream, so production
stalls can be attributed without instrumenting the code. The Python host's
analogue samples the TARGET THREAD's frame stack from a background thread
(sys._current_frames — no signal needed, safe with the GIL), aggregates
(function, file, line) counts and flame-style stacks, and dumps the top
entries through a TraceEvent on stop.

Use it programmatically:

    p = SamplingProfiler(interval=0.005)
    p.start()
    ...
    report = p.stop()       # [(frames_tuple, count)] hottest first
    p.trace_report()        # emits ProfilerReport trace events

SlowTaskWatch is the always-on use of the same stack walk: Net2's SlowTask
(flow/Net2.actor.cpp), a sample of the loop thread's stack taken only while
the loop is being held.
"""

from __future__ import annotations

import sys
import threading
import time
import weakref


def thread_stack(thread_id: int, max_depth: int = 40) -> tuple | None:
    """The thread's running stack, outermost frame first, as
    (function, file, line) triples; None when the thread is gone."""
    f = sys._current_frames().get(thread_id)
    if f is None:
        return None
    stack = []
    while f is not None and len(stack) < max_depth:
        code = f.f_code
        stack.append((code.co_name, code.co_filename, f.f_lineno))
        f = f.f_back
    return tuple(reversed(stack))


class SamplingProfiler:
    def __init__(self, interval: float = 0.005, target_thread: int | None = None,
                 max_depth: int = 40):
        self.interval = interval
        self.target_thread = target_thread or threading.main_thread().ident
        self.max_depth = max_depth
        self.samples: dict[tuple, int] = {}
        self.total_samples = 0
        self._running = False
        self._thread: threading.Thread | None = None

    def start(self):
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(target=self._sample_loop,
                                        name="fdbtpu-profiler", daemon=True)
        self._thread.start()

    def _sample_loop(self):
        while self._running:
            key = thread_stack(self.target_thread, self.max_depth)
            if key is not None:
                self.samples[key] = self.samples.get(key, 0) + 1
                self.total_samples += 1
            time.sleep(self.interval)

    def stop(self) -> list[tuple[tuple, int]]:
        self._running = False
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        return sorted(self.samples.items(), key=lambda kv: -kv[1])

    def hottest_functions(self, top: int = 10) -> list[tuple[str, int]]:
        """Leaf-function attribution: which function was EXECUTING."""
        counts: dict[str, int] = {}
        for stack, n in self.samples.items():
            name, filename, _line = stack[-1]
            label = f"{name} ({filename.rsplit('/', 1)[-1]})"
            counts[label] = counts.get(label, 0) + n
        return sorted(counts.items(), key=lambda kv: -kv[1])[:top]

    def trace_report(self, top: int = 10, who: str = "profiler"):
        """Dump the hottest leaves through the trace stream (the reference
        writes its samples into the trace the same way)."""
        from foundationdb_tpu.utils.trace import TraceEvent
        for label, n in self.hottest_functions(top):
            TraceEvent("ProfilerSample", who) \
                .detail("Where", label) \
                .detail("Samples", n) \
                .detail("Fraction", round(n / max(1, self.total_samples), 4)) \
                .log()


class SlowTaskWatch(threading.Thread):
    """Samples a RealEventLoop's thread while the loop is being held.

    The loop stamps `beat` from a timer that re-arms itself; this thread
    wakes every `interval` and, when the stamp is older than the
    SLOW_TASK_THRESHOLD knob although the loop is running, leaves the loop
    thread's stack in `loop.held`. The loop itself reports the stretch when
    its heartbeat fires again (net/transport.RealEventLoop._heartbeat), so
    every trace record and counter is still written on the loop thread. A
    loop that is not running (between two run_future calls) is idle, not
    held. The thread ends with its loop: it keeps a weak reference only."""

    def __init__(self, loop, interval: float):
        super().__init__(name="fdbtpu-slowtask", daemon=True)
        self._loop = weakref.ref(loop)
        self._thread_id = threading.get_ident()  # built on the loop thread
        self.interval = interval

    def run(self):
        from foundationdb_tpu.utils.knobs import KNOBS
        idle_at = 0.0  # when the loop was last seen not running
        while True:
            time.sleep(self.interval)
            loop = self._loop()
            if loop is None or loop.aio.is_closed():
                return
            now = time.monotonic()
            if not loop.aio.is_running():
                idle_at = now
            else:
                beat = loop.beat
                since = max(beat, idle_at)
                if (loop.held is None
                        and now - since > KNOBS.SLOW_TASK_THRESHOLD):
                    stack = thread_stack(self._thread_id)
                    if stack is not None:
                        loop.held = (beat, since, stack)
            del loop
