"""Start and stop the real process cluster a configuration describes: one core
process (master + resolver + tlog + ratekeeper + commit proxy) that holds the
chips, and one storage process per shard and replica. The launcher and the
role specifications are a copy of `bench_e2e._boot_cluster` / `_spawn_server`
(merged topology), cut to what a configuration's file can state; the core is
started through core_main.py so that run.py can ask it for a profile.

The parent stays off JAX while any child lives: a process that has touched
JAX holds the chip, and the core would fail or hang. `assert_off_jax` says so.
"""

from __future__ import annotations

import json
import os
import selectors
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def assert_off_jax() -> None:
    if "jax" in sys.modules:
        raise RuntimeError("the benchmark's parent imported jax while server "
                           "children may hold the chip")


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Cluster:
    """The running servers. `core` is the address of the core process, which
    is also the one commit proxy; `teams` has one address list per shard."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []
        self.labels: list[str] = []
        self.core = ""
        self.storages: list[str] = []
        self.teams: list[list[str]] = []
        self.boundaries: list[bytes] = []

    # -- words with the core (core_main.py) --

    def ask_core(self, timeout: float = 60.0, **word) -> dict:
        p = self.procs[0]
        p.stdin.write((json.dumps(word) + "\n").encode())
        p.stdin.flush()
        line = read_line(p, time.monotonic() + timeout)
        return json.loads(line)

    def stop(self, grace: float = 60.0) -> None:
        """SIGTERM every server, wait for each, kill what does not go."""
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + grace
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            for f in (p.stdin, p.stdout):
                if f is not None:
                    f.close()
        self.procs = []


def read_line(p: subprocess.Popen, deadline: float) -> bytes:
    sel = selectors.DefaultSelector()
    sel.register(p.stdout, selectors.EVENT_READ)
    buf = b""
    try:
        while not buf.endswith(b"\n"):
            budget = deadline - time.monotonic()
            if budget <= 0:
                raise TimeoutError("no line from the server in time")
            if not sel.select(timeout=min(budget, 5.0)):
                continue
            chunk = os.read(p.stdout.fileno(), 1)  # leave later lines be
            if not chunk:
                raise RuntimeError("the server closed its stdout (it died)")
            buf += chunk
    finally:
        sel.close()
    return buf


def boot(config: dict, cut_keys: list[bytes], run_dir: str, spans: bool,
         core_entry: list[str] | None = None,
         storage_entry: list[str] | None = None,
         env_extra: dict | None = None) -> Cluster:
    """Spawn the servers of `config["cluster"]` and return at once; call
    `wait_ready` before the first request. Servers' stderr goes to
    `<run_dir>/<label>.stderr`; span files to `<run_dir>/spans` if `spans`.
    `core_entry`/`storage_entry` replace the commands that start a server (the
    tests plant faults that way); the spec's JSON is appended to them."""
    from foundationdb_tpu.server.interfaces import Token
    assert_off_jax()
    layout = config["cluster"]
    shards, replicas = int(layout["storage_shards"]), int(layout["replicas"])
    if len(cut_keys) != shards - 1:
        raise ValueError(f"{shards} shards need {shards - 1} cut keys")
    knobs = dict(config["knobs"])
    knobs["CONFLICT_BACKEND"] = layout["conflict_backend"]
    if layout.get("conflict_shards"):
        knobs["CONFLICT_NUM_SHARDS"] = int(layout["conflict_shards"])

    c = Cluster()
    c.core = f"127.0.0.1:{free_port()}"
    c.storages = [f"127.0.0.1:{free_port()}" for _ in range(shards * replicas)]
    c.teams = [c.storages[s * replicas:(s + 1) * replicas]
               for s in range(shards)]
    c.boundaries = [b""] + list(cut_keys)
    shard_spec = {"boundaries": [b.hex() for b in c.boundaries],
                  "tags": [[s * replicas + r for r in range(replicas)]
                           for s in range(shards)]}
    proxy = {"role": "proxy", "args": {
        "proxy_id": 0, "n_proxies": 1, "other_proxies": [],
        "master": {"address": c.core,
                   "token": Token.MASTER_GET_COMMIT_VERSION},
        "resolvers": {"boundaries": [b"".hex()],
                      "endpoints": [{"address": c.core,
                                     "token": Token.RESOLVER_RESOLVE}]},
        "tlogs": [{"address": c.core, "token": Token.TLOG_COMMIT}],
        "shards": shard_spec, "ratekeeper": c.core}}
    core_spec = {
        "listen": c.core, "data_dir": os.path.join(run_dir, "core"),
        "knobs": knobs,
        "roles": [{"role": "master", "args": {}},
                  {"role": "resolver", "args": {"n_proxies": 1}},
                  {"role": "tlog", "args": {}},
                  {"role": "ratekeeper", "args": {
                      "tlogs": [c.core], "storages": c.storages,
                      "resolvers": [c.core]}},
                  proxy]}
    storage_knobs = {k: v for k, v in knobs.items()
                     if not k.startswith(("CONFLICT_", "COMMIT_"))}
    storage_specs = [{
        "listen": addr, "data_dir": os.path.join(run_dir, f"storage{t}"),
        "knobs": storage_knobs,
        "roles": [{"role": "storage",
                   "args": {"tag": t, "tlog_addrs": [c.core]}}]}
        for t, addr in enumerate(c.storages)]

    # only the core may see the chips; every other process is held to the CPU
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    if spans:
        env["FDBTPU_TRACE_DIR"] = os.path.join(run_dir, "spans")
        os.makedirs(env["FDBTPU_TRACE_DIR"], exist_ok=True)
    else:
        env.pop("FDBTPU_TRACE_DIR", None)
    core_env = dict(env)
    if layout["conflict_backend"] != "oracle" and not (env_extra or {}).get(
            "JAX_PLATFORMS"):
        core_env.pop("JAX_PLATFORMS")

    core_cmd = list(core_entry or [sys.executable,
                                   os.path.join(HERE, "core_main.py")])
    storage_cmd = list(storage_entry or [
        sys.executable, "-m", "foundationdb_tpu.net.server_main"])

    def spawn(cmd, spec, env, label, more=()):
        with open(os.path.join(run_dir, f"{label}.stderr"), "wb") as err:
            c.procs.append(subprocess.Popen(
                cmd + [json.dumps(spec)] + list(more), cwd=ROOT, env=env,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
                bufsize=0))
        c.labels.append(label)

    try:
        spawn(core_cmd, core_spec, core_env, "core",
              ["--spans"] if spans else [])
        for t, spec in enumerate(storage_specs):
            spawn(storage_cmd, spec, env, f"storage{t}")
    except BaseException:
        c.stop(grace=5.0)
        raise
    return c


def wait_ready(c: Cluster, deadline_seconds: float) -> None:
    """Every server's `ready` line, or the whole cluster is taken down."""
    deadline = time.monotonic() + deadline_seconds
    try:
        for p, label in zip(c.procs, c.labels):
            try:
                line = read_line(p, deadline)
            except (TimeoutError, RuntimeError) as e:
                raise RuntimeError(f"server {label} did not boot: {e}") from e
            if not line.startswith(b"ready"):
                raise RuntimeError(f"server {label} said {line[:120]!r}")
    except BaseException:
        c.stop(grace=10.0)
        raise


def stderr_tail(run_dir: str, label: str, n: int = 3000) -> str:
    try:
        with open(os.path.join(run_dir, f"{label}.stderr"), "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError as e:
        return f"<{e}>"


def connect(c: Cluster):
    """A client of the cluster in this process: (loop, transport, db)."""
    from foundationdb_tpu.client.database import Database, LocationCache
    from foundationdb_tpu.net.transport import NetTransport, RealEventLoop
    loop = RealEventLoop()
    client = NetTransport(loop, f"127.0.0.1:{free_port()}")
    client.start()
    db = Database(client.process, proxies=[c.core],
                  locations=LocationCache(list(c.boundaries),
                                          [list(t) for t in c.teams]),
                  grv_proxies=[])
    return loop, client, db


def fetch_metrics(loop, client, address: str, token) -> dict:
    """One role's counters over the wire (RESOLVER_METRICS, STORAGE_METRICS)."""
    from foundationdb_tpu.core.sim import Endpoint

    async def fetch():
        return dict(await loop.timeout(client.process.net.request(
            client.process, Endpoint(address, token), None), 10.0))
    return loop.run_future(loop.spawn(fetch()), max_time=30.0)
