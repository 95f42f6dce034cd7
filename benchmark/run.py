"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Knows no cell, configuration or metric by name: the cell names its
configuration (`configs/<config>.json`, by the `file` BENCHMARK.json gives) and
its traffic mix (`traffic/<mix>.json`); each metric, end-to-end or per-layer,
names its reader (`metrics/<metric>.json` -> `readers/<reader>.py`).

Set-up: native extension (built only if missing or stale), the cluster's boot
with the warm-up of the bucket programs, the load, the client workers' start.
The window opens when every actor of every worker has had one acknowledgement,
and lasts `--seconds`. Then the workers drain, every record is read back, the
core's peak memory is read, the cluster is stopped, and the reference
(reference.py) judges what the clients saw. Last line of stdout: the result.

A missing chip is an error: with JAX_PLATFORMS=cpu, or when the core reports
another platform than an accelerator or fewer devices than the cell's `chips`,
nothing is measured and the exit code is not 0.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up counts from the process's start

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

BOOT_DEADLINE_SECONDS = 1100  # a cold core compiles its bucket programs
TRACE_SECONDS = 3.0


def say(kind: str, **fields) -> None:
    """An earlier line of the run: one JSON object, never the last."""
    print(json.dumps({"line": kind, **fields}, default=str), flush=True)


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero, print no result."""


def load_cell(root: str, name: str, bench_file: str | None = None
              ) -> tuple[dict, dict, dict, dict, dict]:
    with open(bench_file or os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(has: {', '.join(sorted(cells))})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, entry, config, mix


class Heartbeat(threading.Thread):
    """This process has nothing to do while the window runs: a gap in its
    own 20 ms ticks is the machine standing still, not the program."""

    def __init__(self):
        super().__init__(name="heartbeat", daemon=True)
        self.gaps: list[tuple[float, float]] = []
        self.stop = threading.Event()

    def run(self) -> None:
        last = time.monotonic()
        while not self.stop.wait(0.02):
            now = time.monotonic()
            if now - last > 0.25:
                self.gaps.append((last, now - last))
            last = now


def _wait_line(proc: subprocess.Popen, want: str, deadline: float) -> str:
    from cluster import read_line
    line = read_line(proc, deadline).decode().strip()
    if want and line != want:
        raise RuntimeError(f"a client worker said {line[:200]!r}, "
                           f"not {want!r}")
    return line


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, core_entry: list[str] | None = None,
             storage_entry: list[str] | None = None,
             env_extra: dict | None = None,
             bench_file: str | None = None) -> dict:
    """Everything between the arguments and the result's line. Returns the
    result (the caller prints it). `require_chip=False`, `bench_file` and the
    `*_entry` arguments are for benchmark/tests, which rehearse on the CPU."""
    import numpy as np

    import cluster as cl
    from reference import LIMITS, check_history, judge
    from traffic import Data, Traffic

    bench, cell, _entry, config, mix = load_cell(root, workload, bench_file)
    chips = int(cell["chips"])
    run_dir = os.path.join(root, ".bench_run", workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    say("start", workload=workload, seed=seed, seconds=seconds, trace=trace,
        cpu_count=os.cpu_count(), affinity=sorted(os.sched_getaffinity(0)),
        pid=os.getpid(),
        compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"])

    t0 = time.monotonic()
    from foundationdb_tpu import native
    if not native.available():
        raise Refused(f"the native extension did not build: "
                      f"{native.build_error()}")
    say("native", seconds=round(time.monotonic() - t0, 3))
    from foundationdb_tpu.server.interfaces import Token
    from foundationdb_tpu.utils import trace as program_trace

    from core_main import drop_spans
    program_trace.set_sink(drop_spans)  # this process's own client's spans

    data = Data(config["data"], seed)
    traffic = Traffic(mix, data)
    shards = int(config["cluster"]["storage_shards"])
    t0 = time.monotonic()
    c = cl.boot(config, data.cut_keys(shards), run_dir, spans=trace,
                core_entry=core_entry, storage_entry=storage_entry,
                env_extra=env_extra)
    workers: list[subprocess.Popen] = []
    client = None
    try:
        # while the servers boot: the data, the pool, the client workers
        initial = data.initial_values()
        pool = traffic.make_pool(seed)
        loaded_bytes = sum(map(len, data.keys)) + sum(map(len, initial))
        n_workers = int(mix["clients"]["processes"])
        n_actors = int(mix["clients"]["actors_per_process"])
        worker_env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
        for w in range(n_workers):
            spec = {"seed": seed, "worker": w, "actors": n_actors,
                    "data": config["data"], "traffic": mix,
                    "listen": f"127.0.0.1:{cl.free_port()}",
                    "proxies": [c.core],
                    "boundaries": [b.hex() for b in c.boundaries],
                    "teams": c.teams,
                    "span_dir": os.path.join(run_dir, "spans") if trace else None,
                    "log": os.path.join(run_dir, f"worker{w}.npy"),
                    "max_seconds": seconds + 600.0}
            with open(os.path.join(run_dir, f"worker{w}.stderr"), "wb") as err:
                workers.append(subprocess.Popen(
                    [sys.executable, os.path.join(HERE, "client_worker.py"),
                     json.dumps(spec)], cwd=root, env=worker_env,
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    stderr=err, bufsize=0))
        try:
            cl.wait_ready(c, BOOT_DEADLINE_SECONDS)
        except RuntimeError as e:
            sys.stderr.write(cl.stderr_tail(run_dir, "core") + "\n")
            raise Refused(f"the cluster did not boot: {e}") from e
        boot_seconds = time.monotonic() - t0
        say("processes", affinity={
            label: sorted(os.sched_getaffinity(p.pid))
            for label, p in zip(c.labels + [f"worker{w}" for w in range(
                n_workers)], c.procs + workers)})
        loop, client, db = cl.connect(c)
        m_boot = cl.fetch_metrics(loop, client, c.core, Token.RESOLVER_METRICS)
        device = {"platform": m_boot.get("Platform"),
                  "kind": m_boot.get("DeviceKind"),
                  "count": m_boot.get("DeviceCount")}
        say("boot", seconds=round(boot_seconds, 3), **{k: m_boot.get(k) for k in (
            "Backend", "Platform", "DeviceKind", "DeviceCount",
            "WarmupSeconds", "CompileCacheHits", "CompileCacheMisses",
            "PersistentCacheHits", "PersistentCacheMisses")})
        if require_chip and (device["platform"] in (None, "cpu")
                             or (device["count"] or 0) < chips):
            raise Refused(f"the core serves on {device}, the cell needs "
                          f"{chips} accelerator chip(s)")

        # ---- load
        t0 = time.monotonic()
        per_txn = int(config["load"]["sets_per_txn"])
        chunks = [range(i, min(i + per_txn, data.count))
                  for i in range(0, data.count, per_txn)]
        nxt = [0]

        async def loader():
            while nxt[0] < len(chunks):
                mine = chunks[nxt[0]]
                nxt[0] += 1

                async def body(tr, mine=mine):
                    for i in mine:
                        tr.set(data.keys[i], initial[i])
                await db.transact(body)

        async def load():
            for t in [loop.spawn(loader(), name=f"load{i}")
                      for i in range(int(config["load"]["loaders"]))]:
                await t

        loop.run_future(loop.spawn(load()), max_time=900.0)
        say("load", records=data.count, bytes=loaded_bytes,
            transactions=len(chunks), seconds=round(time.monotonic() - t0, 3))

        # ---- the window
        deadline = time.monotonic() + 120.0
        for p in workers:
            _wait_line(p, "ready", deadline)
        for p in workers:
            p.stdin.write(b"GO\n")
        t_go = time.monotonic()
        deadline = t_go + 120.0
        for p in workers:
            _wait_line(p, "first_acks", deadline)
        first_acks_seconds = time.monotonic() - t_go
        # the mix's warm-up: traffic runs this long before the window opens,
        # so that batching, retries and throttling are in their steady state
        time.sleep(max(0.0, t_go + float(mix["warm_seconds"])
                       - time.monotonic()))
        say("warm", first_acks_seconds=round(first_acks_seconds, 3),
            warm_seconds=round(time.monotonic() - t_go, 3))
        storages = [a for team in c.teams for a in team]
        m0 = cl.fetch_metrics(loop, client, c.core, Token.RESOLVER_METRICS)
        s0 = [cl.fetch_metrics(loop, client, a, Token.STORAGE_METRICS)
              for a in storages]
        t_open = time.monotonic() + 0.05
        t_close = t_open + seconds
        for p in workers:
            p.stdin.write(f"WINDOW {t_open!r} {t_close!r}\n".encode())
        setup_s = t_open - T_START
        heartbeat = Heartbeat()
        heartbeat.start()
        # the profile is taken over the window's last seconds and closed once
        # the window's counters are read: stop_trace holds the core for some
        # 20 s (chip run, PR 25), which then falls into the drain
        traced = None
        if trace:
            span = min(TRACE_SECONDS, seconds * 0.5)
            time.sleep(max(0.0, t_close - span - time.monotonic()))
            trace_dir = os.path.join(run_dir, "profile")
            traced = {"dir": trace_dir, "asked_seconds": span,
                      "start": c.ask_core(cmd="trace_start", dir=trace_dir)}
        time.sleep(max(0.0, t_close - time.monotonic()))
        m1 = cl.fetch_metrics(loop, client, c.core, Token.RESOLVER_METRICS)
        s1 = [cl.fetch_metrics(loop, client, a, Token.STORAGE_METRICS)
              for a in storages]
        t_fetched = time.monotonic()
        if traced:
            traced["stop"] = c.ask_core(cmd="trace_stop", timeout=240.0)
            traced["stop_took"] = round(time.monotonic() - t_fetched, 3)
            say("profile", **traced)

        # ---- drain: every answer that is due is waited for
        summaries = []
        deadline = time.monotonic() + 180.0
        for p in workers:
            summaries.append(json.loads(_wait_line(p, "", deadline)))
        for p in workers:
            p.wait(timeout=60)
        heartbeat.stop.set()
        say("workers", summaries=summaries,
            drained_after_close=round(time.monotonic() - t_close, 3))
        errors: dict[str, int] = {}
        for summary in summaries:
            for name, n in summary["errors"].items():
                errors[name] = errors.get(name, 0) + n
        say("errors", other_than_not_committed=errors,
            parent_stood_still=[[round(at - t_open, 2), round(gap, 3)]
                                for at, gap in heartbeat.gaps])

        # ---- read every record back from storage
        t0 = time.monotonic()
        readback: dict[int, bytes] = {}
        strays = [0]

        async def read_back():
            begin = b""
            while True:
                async def body(tr, begin=begin):
                    return await tr.get_range(begin, b"\xff", limit=2000)
                rows = await db.transact(body)
                for k, v in rows:
                    i = data.index.get(k)
                    if i is None:
                        strays[0] += 1
                        readback[-strays[0]] = v
                    else:
                        readback[i] = v
                if len(rows) < 2000:
                    return
                begin = rows[-1][0] + b"\x00"

        loop.run_future(loop.spawn(read_back()), max_time=600.0)
        say("readback", records=len(readback),
            seconds=round(time.monotonic() - t0, 3))
        m_end = cl.fetch_metrics(loop, client, c.core, Token.RESOLVER_METRICS)
        memory = c.ask_core(cmd="memory")
        say("core", **memory)
        client.close()
        client = None
    except BaseException:
        for p in workers:
            if p.poll() is None:
                p.kill()
        for label in c.labels:
            tail = cl.stderr_tail(run_dir, label, 1500)
            if tail.strip():
                sys.stderr.write(f"--- {label}.stderr\n{tail}\n")
        raise
    finally:
        if client is not None:
            client.close()
        for p in workers:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                f.close()
        c.stop()
        cl.assert_off_jax()

    # ---- the window's transactions, from the workers' raw rows
    logs = {w: np.load(os.path.join(run_dir, f"worker{w}.npy"))
            for w in range(n_workers)}
    rows = np.concatenate(list(logs.values()))
    inside = rows[(rows["t1"] >= t_open) & (rows["t1"] <= t_close)]
    acked = inside[inside["status"] == 0]
    failed = int(len(inside) - len(acked))
    lat_ms = (inside["t1"] - inside["t0"]) * 1e3
    lat_ms[inside["status"] != 0] = np.inf  # a failure misses every limit
    writing = inside["writes"] == 1
    samples = {"commit": lat_ms[writing].tolist(),
               "read": lat_ms[~writing].tolist()}
    # steadiness at a glance: acknowledgements a second of the whole run,
    # the seconds counted from the window's open (negative: before it)
    sec = np.floor(rows["t1"] - t_open).astype(int)
    say("window", open=t_open, close=t_close)
    say("series", per_second={int(s): int(n) for s, n in zip(
        *np.unique(sec, return_counts=True))})
    say("samples", finished_in_window=int(len(inside)), failed=failed,
        writing=len(samples["commit"]), read_only=len(samples["read"]),
        ran_in_all=int(len(rows)), window_seconds=seconds)
    resolver_delta = {k: m1[k] - m0[k] for k in (
        "BatchesIn", "TxnResolved", "KernelDispatches", "HostExactChunks",
        "ReadbackWaitSeconds", "DrainGroups", "ConflictsSampled")
        if isinstance(m0.get(k), (int, float))
        and not isinstance(m0.get(k), bool)}
    say("resolver", delta=resolver_delta, fetched_after_close=round(
        t_fetched - t_close, 4), Poisoned=m_end.get("Poisoned"),
        PersistentCacheHits=m_end.get("PersistentCacheHits"),
        PersistentCacheMisses=m_end.get("PersistentCacheMisses"),
        CompileCacheMisses_in_window=(m1.get("CompileCacheMisses", 0)
                                      - m0.get("CompileCacheMisses", 0)))

    # ---- correct: the reference's answer to what the clients saw
    t0 = time.monotonic()
    numbers, notes = check_history(traffic, seed, pool, initial, logs, readback)
    limits = dict(LIMITS)
    if require_chip:
        # the chip decided every batch, or the run measured something else
        numbers["resolver_poisoned"] = int(bool(m_end.get("Poisoned")))
        numbers["batches_off_kernel"] = max(
            0, m_end.get("BatchesIn", 0) - m_end.get("KernelDispatches", 0))
        numbers["window_batches_missing"] = int(
            resolver_delta.get("BatchesIn", 0) <= 0)
        limits.update(resolver_poisoned=0, batches_off_kernel=0,
                      window_batches_missing=0)
    correct, compared = judge(numbers, limits)
    say("reference", seconds=round(time.monotonic() - t0, 3), **notes)

    ctx = {"root": root, "run_dir": run_dir, "config": config, "mix": mix,
           "window": (t_open, t_close), "seconds": seconds,
           "setup_s": setup_s, "samples": samples,
           "acknowledged_ops": int(len(acked)) * traffic.ops_per_txn,
           "resolver": (m0, m1), "storage": list(zip(s0, s1)),
           "workers": summaries, "rows": inside, "device": device,
           "profile_dir": traced["dir"] if traced else None}
    result = {"correct": correct, "attempted": int(len(inside)),
              "failed": failed,
              "metrics": read_metrics(
                  bench["per_layer" if trace else "end_to_end"], workload,
                  ctx, must=not trace),
              "device": dict(device, memory_peak_bytes=memory.get(
                  "memory_peak_bytes"))}
    if trace:
        extra = _reader("xplane").device_summary(ctx)
        result["device"].update(extra.get("device", {}))
        say("programs", **extra.get("programs", {}))
        if "breakdown" in extra:
            result["breakdown"] = extra["breakdown"]
    result["compared"] = compared
    for name, cmp in compared.items():
        sys.stderr.write(f"compared {name} = {cmp['value']} "
                         f"(limit {cmp['limit']})\n")
    sys.stderr.flush()
    return result


def read_metrics(entries: list[dict], workload: str, ctx: dict,
                 must: bool) -> dict:
    """The cell's metrics among `entries` of BENCHMARK.json, each by the
    reader its own file names (`metrics/<metric>.json`). A reader that finds
    nothing to read returns None: a per-layer metric is then left out of the
    line, and an end-to-end one (`must`) makes the run no measurement."""
    metrics = {}
    for m in entries:
        if workload not in m.get("workloads", [workload]):
            continue
        with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        value = _reader(spec["reader"]).read(ctx, **spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif must:
            raise Refused(f"the run has no {m['name']}")
    return metrics


def _reader(name: str):
    return importlib.import_module(f"readers.{name}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    try:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            raise Refused("JAX_PLATFORMS=cpu: the benchmark measures on the "
                          "accelerator and has no CPU mode")
        if not os.path.isdir(os.path.join(ROOT, "foundationdb_tpu")):
            raise Refused("no program beside the benchmark: nothing to run")
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        sys.stderr.write(f"benchmark/run.py: refused: {e}\n")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
