"""Server process entry point: run roles over the real transport.

Reference: fdbserver/fdbserver.actor.cpp main + worker.actor.cpp — one OS
process hosts a set of roles listening on one address. The role spec comes in
as JSON on argv (the stand-in for command-line flags + cluster file):

  python -m foundationdb_tpu.net.server_main '{"listen": "127.0.0.1:4500",
      "data_dir": "/tmp/x", "knobs": {"CONFLICT_BACKEND": "oracle"},
      "roles": [{"role": "master", ...}, ...]}'

Role args mirror the sim worker's InitRoleRequest args, with endpoint
dictionaries {"address": ..., "token": ...} converted to Endpoints.
"""

from __future__ import annotations

import json
import sys


def _to_endpoint(v):
    from foundationdb_tpu.core.sim import Endpoint
    if isinstance(v, dict) and set(v) == {"address", "token"}:
        return Endpoint(v["address"], v["token"])
    if isinstance(v, list):
        return [_to_endpoint(x) for x in v]
    return v


def build_role(process, role: str, args: dict):
    args = {k: _to_endpoint(v) for k, v in args.items()}
    if role == "master":
        from foundationdb_tpu.server.master import Master
        return Master(process, **args)
    if role == "proxy":
        from foundationdb_tpu.server.proxy import Proxy, ResolverMap, ShardMap
        args["resolvers"] = ResolverMap(
            boundaries=[bytes.fromhex(b) for b in args["resolvers"]["boundaries"]],
            endpoints=_to_endpoint(args["resolvers"]["endpoints"]))
        args["shards"] = ShardMap(
            boundaries=[bytes.fromhex(b) for b in args["shards"]["boundaries"]],
            tags=args["shards"]["tags"])
        return Proxy(process, **args)
    if role == "grv_proxy":
        from foundationdb_tpu.server.proxy import Proxy
        return Proxy(process, grv_only=True, **args)
    if role == "resolver":
        from foundationdb_tpu.server.resolver import Resolver
        # key range rides the JSON spec hex-encoded (bytes aren't JSON);
        # absent/None end = "to the end of keyspace"
        if "key_range_begin" in args:
            args["key_range_begin"] = bytes.fromhex(args["key_range_begin"])
        if args.get("key_range_end") is not None:
            args["key_range_end"] = bytes.fromhex(args["key_range_end"])
        return Resolver(process, **args)
    if role == "tlog":
        from foundationdb_tpu.server.tlog import TLog
        t = TLog(process, **args)
        t.recover_from_file()  # real deployments reboot onto surviving files
        return t
    if role == "storage":
        from foundationdb_tpu.server.storage import StorageServer
        return StorageServer(process, **args)
    if role == "ratekeeper":
        from foundationdb_tpu.server.ratekeeper import Ratekeeper
        return Ratekeeper(process, **args)
    raise ValueError(f"unknown role {role!r}")


# Net allocations of container objects before a young collection (the
# interpreter's default is 700, and a full collection follows every hundredth
# young one). At 700 a commit batch's few thousand short-lived containers
# (requests, ranges, futures, span records) are alive at several young
# collections of their own batch and are promoted into the generation a full
# collection walks; with the heap frozen the core still made 48-50 full
# collections a run, each over the batches in flight. At 50,000 a batch is
# born and dies young and a whole run makes none: +4% operations a second
# over freeze alone where the key space is large (PERF.md section 6, PR 35).
YOUNG_GENERATION_THRESHOLD = 50_000


def settle_heap():
    """The collector's policy of a server process, set once when every role
    is built and before `ready`. Boot's garbage goes; everything alive now
    (the modules, JAX, the loaded programs' Python side, the roles) can never
    be garbage while the process serves, so it moves to the permanent
    generation and no full collection walks it again; what is allocated from
    here on (a storage server's records too) stays collectable. The watcher
    goes in after the boot's own collection, so the counters say what
    collections cost while serving."""
    import gc

    from foundationdb_tpu.utils import stats, trace
    gc.collect()
    gc.freeze()
    gc.set_threshold(YOUNG_GENERATION_THRESHOLD, *gc.get_threshold()[1:])
    stats.frozen_objects.set(gc.get_freeze_count())
    trace.span_full_collections()


def main(spec_json: str):
    from foundationdb_tpu.net.transport import NetTransport, RealEventLoop
    from foundationdb_tpu.utils.jaxenv import enable_compile_cache
    from foundationdb_tpu.utils.knobs import KNOBS

    enable_compile_cache()
    spec = json.loads(spec_json)
    for k, v in spec.get("knobs", {}).items():
        KNOBS.set(k, v)
    loop = RealEventLoop()
    net = NetTransport(loop, spec["listen"],
                       data_dir=spec.get("data_dir", "/tmp/fdbtpu"))
    net.start()
    # TLogs boot first so '@recover:local_tlog' args can fence version
    # allocation past what this process's logs durably reached — the static-
    # topology stand-in for coordinated recovery (a restarted master that
    # re-issues old versions would be silently ignored by storage; the
    # reference's master always recovers its version from the log system,
    # masterserver.actor.cpp recoverFrom).
    ordered = sorted(spec["roles"],
                     key=lambda r: 0 if r["role"] in ("tlog", "storage") else 1)
    roles = []
    built = {}
    for r in ordered:
        args = dict(r.get("args", {}))
        for k, v in args.items():
            if v == "@recover:local_tlog":
                tlogs = built.get("tlog", [])
                args[k] = max((t.version.get() for t in tlogs), default=0)
        role = build_role(net.process, r["role"], args)
        built.setdefault(r["role"], []).append(role)
        roles.append(role)
    settle_heap()
    print(f"ready {spec['listen']} roles={[r['role'] for r in spec['roles']]}",
          flush=True)
    import os
    import signal
    # graceful SIGTERM always: unwind through finally so the transport
    # closes and, on device-backend servers, the accelerator client is
    # destroyed cleanly before the next process asks for the chip
    signal.signal(signal.SIGTERM,
                  lambda *_a: loop.aio.call_soon_threadsafe(loop.aio.stop))
    prof_path = os.environ.get("FDBTPU_PROFILE")
    if prof_path:
        import cProfile
        pr = cProfile.Profile()
        pr.enable()
    trace_file = None
    trace_dir = os.environ.get("FDBTPU_TRACE_DIR")
    if trace_dir:
        # per-process rolling trace file (openTraceFile): span/counter
        # records land here instead of stderr, named by listen address so
        # trace_analyze can merge the whole cluster's files
        from foundationdb_tpu.utils import trace
        trace_file = trace.RollingTraceFile(os.path.join(
            trace_dir, f"trace.{spec['listen'].replace(':', '_')}.jsonl"))
        trace.set_sink(trace_file.write)
    try:
        loop.aio.run_forever()
    finally:
        if prof_path:
            pr.disable()
            pr.dump_stats(f"{prof_path}.{spec['listen'].replace(':', '_')}")
        if trace_file is not None:
            from foundationdb_tpu.utils.stats import whole_process_counters
            from foundationdb_tpu.utils.trace import g_trace_batch, set_sink
            # final counter dump: a short run may never reach the periodic
            # 5s tick, and the rollup wants end-of-run totals either way
            extra = whole_process_counters(net)
            for role in roles:
                coll = getattr(role, "counters", None)
                if hasattr(coll, "trace"):
                    coll.trace(loop.now(), extra=extra)
            g_trace_batch.dump()  # buffered span records survive shutdown
            set_sink(None)
            trace_file.close()
        net.close()
        del roles


if __name__ == "__main__":
    main(sys.argv[1])
