"""Serve a trained run over HTTP.

    python -m hydragnn_tpu.serve --config logs/<run>/config.json \
        [--logs-dir ./logs/] [--host H] [--port P] \
        [--fleet N [--fleet-inprocess]]

``--config`` is the FINALIZED config run_training saved next to the
checkpoint (it carries output dims, head layout and the written-back
``Serving`` section).  Per-graph bucket sizing must be present —
``Serving.max_nodes_per_graph``/``max_edges_per_graph`` in the config or
the ``HYDRAGNN_SERVE_MAX_NODES``/``HYDRAGNN_SERVE_MAX_EDGES`` env knobs.
Telemetry env knobs (HYDRAGNN_TELEMETRY=1 etc.) give the server a JSONL
event log viewable with tools/teleview.py.

``--fleet N`` (or ``Serving.fleet_replicas``) runs N supervised engine
replicas behind the failover router instead of one server: each replica
is a child ``python -m hydragnn_tpu.serve`` process on an ephemeral
loopback port (``--fleet-inprocess`` keeps them as threads sharing one
compile cache and one device — the ONLY fleet spelling that starts on a
single chip, because every subprocess child would open the chip itself
and a chip belongs to one process), crashed replicas restart with
exponential backoff, and ``POST /reload`` becomes a rolling
one-replica-at-a-time fleet update (docs/SERVING.md "Replica fleet").
``--reload-watch`` applies to single-server mode only.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True,
                    help="finalized config.json from a trained run's log dir")
    ap.add_argument("--logs-dir", default="./logs/",
                    help="logs root holding the checkpoint (default ./logs/)")
    ap.add_argument("--host", default=None, help="bind host override")
    ap.add_argument("--port", type=int, default=None,
                    help="bind port override")
    ap.add_argument("--reload-watch", default=None, metavar="CKPT",
                    help="hot-reload this checkpoint file whenever its "
                         "mtime changes (validated + rollback-protected; "
                         "see docs/SERVING.md)")
    ap.add_argument("--reload-watch-s", type=float, default=None,
                    help="file-watch poll interval in seconds "
                         "(default 5 when --reload-watch is set)")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="run N supervised replicas behind the failover "
                         "router (overrides Serving.fleet_replicas; "
                         "0 = single server)")
    ap.add_argument("--fleet-inprocess", action="store_true",
                    help="fleet replicas as in-process threads sharing "
                         "one compile cache and device (required on a "
                         "single chip) instead of subprocesses")
    args = ap.parse_args(argv)

    with open(args.config) as f:
        config = json.load(f)

    from hydragnn_tpu.serve import InferenceEngine, InferenceServer, \
        ServingConfig
    from hydragnn_tpu.telemetry import MetricsLogger

    serving = ServingConfig.from_section(config.get("Serving"))
    if args.host is not None:
        serving.host = args.host
    if args.port is not None:
        serving.port = args.port
    if args.reload_watch is not None:
        serving.reload_watch_path = args.reload_watch
        # CLI interval > configured (config/env) interval > 5 s default
        serving.reload_watch_s = args.reload_watch_s \
            if args.reload_watch_s is not None \
            else (serving.reload_watch_s or 5.0)
    elif args.reload_watch_s is not None:
        serving.reload_watch_s = args.reload_watch_s
    if args.fleet is not None:
        serving.fleet_replicas = max(0, int(args.fleet))
    if args.fleet_inprocess:
        serving.fleet_inprocess = True
    # the subprocess fleet's router parent must stay off JAX (a parent that
    # has touched JAX holds the chip its children need): its telemetry
    # names no device and it sets up no compile cache — the children do
    router_only = serving.fleet_replicas > 0 and not serving.fleet_inprocess
    telemetry = MetricsLogger.from_env(run_name="serve",
                                       names_device=not router_only)
    if not router_only:
        from hydragnn_tpu.utils.runtime import setup_compile_cache

        setup_compile_cache()

    if serving.fleet_replicas > 0:
        from hydragnn_tpu.resilience import FleetChaos
        from hydragnn_tpu.serve import (
            FleetRouter, FleetSupervisor, InProcessReplica,
            SubprocessReplica, spawn_argv)

        n = serving.fleet_replicas
        if serving.fleet_inprocess:
            base = InferenceEngine.from_config(
                config, logs_dir=args.logs_dir, serving=serving,
                telemetry=telemetry)
            base.warmup()  # forks share this one compiled cache
            replicas = [
                InProcessReplica(i, base.fork, serving, telemetry)
                for i in range(n)
            ]
            cfg, pbc = base.cfg, base.pbc

            def replica_factory(i):
                return InProcessReplica(i, base.fork, serving, telemetry)
        else:
            builder = spawn_argv(args.config, logs_dir=args.logs_dir)
            replicas = [
                SubprocessReplica(i, builder, serving, telemetry)
                for i in range(n)
            ]
            cfg, pbc = None, False

            def replica_factory(i):
                return SubprocessReplica(i, builder, serving, telemetry)
        # fleet_max_replicas > 0 arms the closed-loop autoscaler: the
        # supervisor builds the FleetAutoscaler policy itself and grows
        # or shrinks the fleet via this factory (serve/autoscale.py)
        fleet = FleetSupervisor(replicas, serving, telemetry=telemetry,
                                chaos=FleetChaos.from_env(
                                    config.get("Serving", {}).get(
                                        "FleetChaos")),
                                replica_factory=replica_factory)
        router = FleetRouter(fleet, serving=serving, cfg=cfg, pbc=pbc,
                             telemetry=telemetry)
        mode = "in-process" if serving.fleet_inprocess else "subprocess"
        print(f"fleet of {n} {mode} replicas — router on "
              f"http://{serving.host}:{router.port} — SIGTERM drains "
              "gracefully", flush=True)
        try:
            router.run()
        finally:
            telemetry.finalize()
        return 0

    engine = InferenceEngine.from_config(
        config, logs_dir=args.logs_dir, serving=serving, telemetry=telemetry)
    server = InferenceServer(engine, serving=serving)
    print(f"serving on http://{serving.host}:{server.port}  "
          f"(buckets: {[p.num_graphs - 1 for p in engine.pad_specs]}, "
          f"max_wait {serving.max_wait_ms} ms) — SIGTERM drains gracefully",
          flush=True)
    try:
        server.run()
    finally:
        telemetry.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
