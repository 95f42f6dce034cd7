"""One client worker process: `actors` closed-loop actors over the ordinary
client API (`Database.create_transaction`, `Transaction.get/set/commit`,
`on_error`), against the cluster run.py started. Protocol, one line each way:

    worker -> "ready"                 connected, keys and plans can be made
    run.py -> "GO"                    start every actor
    worker -> "first_acks"            each actor has had an acknowledgement
                                      (or FIRST_ACKS_CAP seconds have passed:
                                      an actor throttled on a hot record may
                                      wait longer than that for its first)
    run.py -> "WINDOW <open> <close>" on time.monotonic(), which all
                                      processes of one machine share
    worker -> one JSON line           its summary, once every actor has
                                      finished the transaction it was in at
                                      <close>; the log is in <log>.npy

The worker starts no transaction after <close>; it keeps none back before
<open>. What it logs is every transaction it ran, inside the window or not:
the reference needs them all, run.py takes the window's.

    python benchmark/client_worker.py '<spec json>'
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
FIRST_ACKS_CAP = 5.0


def main(spec: dict) -> None:
    import numpy as np

    from actor import LOG_DTYPE, run_actor
    from core_main import drop_spans
    from foundationdb_tpu.client.database import Database, LocationCache
    from foundationdb_tpu.net.transport import NetTransport, RealEventLoop
    from foundationdb_tpu.utils import trace
    from traffic import Data, Traffic

    trace_file = None
    if spec.get("span_dir"):
        trace_file = trace.RollingTraceFile(os.path.join(
            spec["span_dir"], f"trace.client{spec['worker']}.jsonl"))
        trace.set_sink(trace_file.write)
    else:
        trace.set_sink(drop_spans)

    seed, worker, n_actors = spec["seed"], spec["worker"], spec["actors"]
    data = Data(spec["data"], seed)
    traffic = Traffic(spec["traffic"], data)
    pool = traffic.make_pool(seed)
    loop = RealEventLoop()
    client = NetTransport(loop, spec["listen"])
    client.start()
    db = Database(client.process, proxies=list(spec["proxies"]),
                  locations=LocationCache(
                      [bytes.fromhex(b) for b in spec["boundaries"]],
                      [list(t) for t in spec["teams"]]),
                  grv_proxies=[])

    state = {"open": None, "close": None}

    def read_window():
        for line in sys.stdin:
            words = line.split()
            if words and words[0] == "WINDOW":
                state["open"], state["close"] = float(words[1]), float(words[2])
                return

    print("ready", flush=True)
    if sys.stdin.readline().strip() != "GO":
        raise SystemExit("client_worker: expected GO")
    threading.Thread(target=read_window, daemon=True).start()

    rows: list[tuple] = []
    errors: dict[str, int] = {}
    acked = [0]
    cpu = {}

    def keep_going() -> bool:
        close = state["close"]
        return close is None or time.monotonic() < close

    def say_first_acks():
        if not state.get("said_first_acks"):
            state["said_first_acks"] = True
            print("first_acks", flush=True)

    def on_first_ack():
        acked[0] += 1
        if acked[0] == n_actors:
            say_first_acks()

    async def cap_first_acks():
        await loop.delay(FIRST_ACKS_CAP)
        say_first_acks()

    def cpu_seconds() -> float:
        t = os.times()
        return t.user + t.system

    async def watch_cpu():
        while state["open"] is None:
            await loop.delay(0.02)
        await loop.delay(max(0.0, state["open"] - time.monotonic()))
        cpu["open"] = (time.monotonic(), cpu_seconds())
        await loop.delay(max(0.0, state["close"] - time.monotonic()))
        cpu["close"] = (time.monotonic(), cpu_seconds())

    async def everything():
        tasks = [loop.spawn(run_actor(
            db, traffic, data.keys, pool,
            traffic.actor_rng(seed, worker, a), a, rows, keep_going,
            on_first_ack, errors), name=f"actor{a}") for a in range(n_actors)]
        tasks.append(loop.spawn(watch_cpu(), name="cpu"))
        tasks.append(loop.spawn(cap_first_acks(), name="cap"))
        for t in tasks:
            await t

    loop.run_future(loop.spawn(everything()), max_time=spec["max_seconds"])
    client.close()
    if trace_file is not None:
        trace.g_trace_batch.dump()
        trace.set_sink(None)
        trace_file.close()
    np.save(spec["log"], np.array(rows, dtype=LOG_DTYPE))
    print(json.dumps({"worker": worker, "txns": len(rows), "cpu": cpu,
                      "errors": errors, "pid": os.getpid()}), flush=True)


if __name__ == "__main__":
    main(json.loads(sys.argv[1]))
