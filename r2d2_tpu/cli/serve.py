"""Standalone policy inference server (ISSUE 13): serve a checkpoint's
policy over TCP (and optionally the shm ring for same-host clients).

    python -m r2d2_tpu.cli.serve --ckpt models/Fake3_player0 --port 5999
    python -m r2d2_tpu.cli.serve --seconds 30            # random-init smoke

The server loop owns the device-resident params and the per-client
state cache; clients are ``serve.RemotePolicy``/``RemoteBatchedPolicy``
over a ``SocketChannel`` (or ``ShmServeChannel`` with ``--shm``). A
periodic record with the ``serving`` block (request latency, batch fill,
client churn) appends to ``serve_metrics.jsonl`` in --save-dir, with the
stock alert rules (``serve_latency_slo``, ``serve_batch_starvation``,
``serve_client_churn``) evaluated per record into
``serve_alerts.jsonl`` — the same SLO plumbing the in-training server
rides. SIGTERM/SIGINT stop cleanly.

With ``serve.servers=N`` (N > 1) the process hosts a sharded serving
FLEET instead: N server loops over client-hash cache slices, one TCP
listener per fleet slot, and the printed ``socket_fleet`` spec is what
clients feed a ``RoutingChannel``. ``serve.queue_depth_bound`` arms
admission control (overflow sheds with retry-after; the
``serve_brownout`` rule fires on the shed fraction).
"""

import argparse
import json
import os
import signal
import sys
import time


def main(argv=None) -> int:
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ckpt", default="",
                   help="checkpoint to serve (empty: random init — smoke "
                        "tests and transport bring-up)")
    p.add_argument("--shm", action="store_true",
                   help="also open the same-host shm ring transport; its "
                        "request-ring name is printed for clients")
    p.add_argument("--seconds", type=float, default=0.0,
                   help="stop after this long (0 = run until signaled)")
    p.add_argument("--save-dir", default=".",
                   help="where serve_metrics.jsonl / serve_alerts.jsonl go")
    args, config_overrides = p.parse_known_args(argv)

    import jax
    import numpy as np

    from r2d2_tpu.config import Config, parse_overrides
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.serve import (InprocEndpoint, PolicyServer, ServingStats,
                                ShmServeTransport, SocketServerTransport)
    from r2d2_tpu.telemetry import Telemetry
    from r2d2_tpu.telemetry.alerts import AlertEngine, default_rules

    cfg = parse_overrides(Config(), config_overrides)
    from r2d2_tpu.utils.platform import announce_runtime
    announce_runtime(cfg)
    if args.ckpt:
        from r2d2_tpu.runtime.checkpoint import (load_checkpoint_config,
                                                 restore_checkpoint)
        stored = load_checkpoint_config(args.ckpt)
        if stored is not None:
            import dataclasses
            cfg = dataclasses.replace(cfg, env=stored.env,
                                      network=stored.network,
                                      sequence=stored.sequence)
    probe = create_env(cfg.env, seed=cfg.runtime.seed)
    action_dim = probe.action_space.n
    probe.close()
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    params = net.init(jax.random.PRNGKey(cfg.runtime.seed))
    if args.ckpt:
        restored = restore_checkpoint(args.ckpt)
        params = jax.tree_util.tree_map(
            lambda t, p_: np.asarray(p_, np.asarray(t).dtype),
            params, restored["params"])

    quant_stats = None
    if cfg.network.inference_dtype != "f32":
        # quantized serving (ISSUE 14): the server builds the twin at
        # construction and probes per dispatch interval; the quant block
        # (dtype, agreement, |ΔQ|) rides every serve_metrics record so
        # the quant_divergence rule evaluates here too
        from r2d2_tpu.telemetry import QuantStats
        quant_stats = QuantStats(cfg.network.inference_dtype,
                                 cfg.telemetry.quant_probe_interval)

    stats = ServingStats()
    tracing = cfg.telemetry.enabled and cfg.telemetry.tracing_enabled
    if tracing:
        # distributed tracing (ISSUE 19): traced requests' per-hop
        # stamps fold into the serving block's trace sub-block
        from r2d2_tpu.telemetry.tracing import ServeTrace
        stats.trace = ServeTrace()
    telemetry = Telemetry.from_config(cfg, name="serve")
    fleet = None
    server = None
    transports = []
    if cfg.serve.servers > 1:
        # sharded serving fleet (ISSUE 17): N server loops, one TCP
        # listener per fleet slot (parked slots included — their
        # listeners bounce MISROUTED so growth never changes an
        # address). The printed spec is exactly what actor_main's
        # socket_fleet branch consumes to build a RoutingChannel.
        if args.shm:
            p.error("--shm is single-server only (serve.servers > 1 "
                    "rejects the shm rung)")
        from r2d2_tpu.serve import ServerFleet
        fleet = ServerFleet(cfg, net, params, stats=stats,
                            telemetry=telemetry, quant_stats=quant_stats)
        spec_servers = {}
        for slot, ep in fleet.serve_spec_servers().items():
            port = cfg.serve.port + slot if cfg.serve.port else 0
            t = SocketServerTransport(ep.submit, cfg.serve.host, port)
            transports.append(t)
            spec_servers[slot] = [t.host, t.port]
        spec = {"transport": "socket_fleet", "servers": spec_servers,
                "total_shards": fleet.total_shards,
                "assign": [fleet.shard_map.version,
                           list(fleet.shard_map.assignment())]}
        print(f"serving fleet of {cfg.serve.servers} "
              f"(max {fleet.max_servers}) — spec: "
              + json.dumps(spec), flush=True)
    else:
        # the server first, the listener second: construction compiles
        # every micro-batch bucket (tens of seconds cold on the chip), and
        # a listener that accepts before the loop can answer turns that
        # wait into client timeouts — "serving on" means it is serving
        endpoint = InprocEndpoint()
        server = PolicyServer(cfg, net, params, endpoint=endpoint,
                              stats=stats, telemetry=telemetry,
                              quant_stats=quant_stats).start()
        transports = [SocketServerTransport(endpoint.submit, cfg.serve.host,
                                            cfg.serve.port)]
        print(f"serving on {transports[0].host}:{transports[0].port} "
              f"(action_dim={action_dim})", flush=True)
        if args.shm:
            shm_t = ShmServeTransport(
                endpoint.submit, (cfg.env.frame_height, cfg.env.frame_width),
                action_dim, net.state_half,
                request_slots=cfg.serve.request_ring_slots,
                tracing=tracing)
            transports.append(shm_t)
            print(f"shm request ring: {shm_t.request_ring.name}", flush=True)

    os.makedirs(args.save_dir or ".", exist_ok=True)
    metrics_path = os.path.join(args.save_dir or ".", "serve_metrics.jsonl")
    open(metrics_path, "w").close()
    engine = AlertEngine(
        default_rules(cfg.telemetry),
        jsonl_path=os.path.join(args.save_dir or ".", "serve_alerts.jsonl"))
    # process identity + clock anchor (ISSUE 19 satellite): stamped ONCE
    # at announcement (the listener going live IS this plane's lease
    # moment) and carried on every periodic row, so the tower join and
    # the Perfetto merge align this stream without a shared mono clock
    from r2d2_tpu.telemetry.tracing import proc_header
    proc = proc_header("serve")
    telemetry.start_drain(
        os.path.join(args.save_dir or ".", "spans_serve.jsonl"))

    def _batches() -> int:
        if server is not None:
            return server.batches_dispatched
        return sum(s.batches_dispatched for s in fleet.servers.values())

    def _serving_block():
        if fleet is not None:
            return fleet.interval_block(deadline_ms=cfg.serve.deadline_ms,
                                        max_batch=cfg.serve.max_batch)
        return stats.interval_block(deadline_ms=cfg.serve.deadline_ms,
                                    max_batch=cfg.serve.max_batch)

    stop = {"flag": False}

    def _on_signal(signum, frame):
        stop["flag"] = True

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_signal)
        except (ValueError, OSError):
            pass

    t0 = time.time()
    last_log = t0
    try:
        while not stop["flag"]:
            if args.seconds and time.time() - t0 >= args.seconds:
                break
            time.sleep(0.2)
            if fleet is not None:
                # fleet supervision on the log-loop cadence: a dead
                # server's shards rehome to survivors (clients re-route
                # off the MISROUTED bounces)
                fleet.supervise()
            now = time.time()
            if now - last_log >= cfg.runtime.log_interval:
                last_log = now
                block = _serving_block()
                record = {"t": round(now - t0, 1),
                          "batches": _batches(), "proc": proc}
                if block is not None:   # the TrainMetrics omission contract
                    record["serving"] = block
                if quant_stats is not None:
                    record["quant"] = quant_stats.interval_block()
                record["alerts"] = engine.evaluate(record)
                with open(metrics_path, "a") as f:
                    f.write(json.dumps(record) + "\n")
    finally:
        final_batches = _batches()
        if server is not None:
            server.stop()
        if fleet is not None:
            fleet.stop()
        for t in transports:
            t.close()
        telemetry.close()
        # final record so short runs still leave evidence
        block = _serving_block()
        record = {"t": round(time.time() - t0, 1),
                  "batches": final_batches, "final": True, "proc": proc}
        if block is not None:
            record["serving"] = block
        if quant_stats is not None:
            record["quant"] = quant_stats.interval_block()
        record["alerts"] = engine.evaluate(record)
        with open(metrics_path, "a") as f:
            f.write(json.dumps(record) + "\n")
        print(f"served {final_batches} batches in "
              f"{time.time() - t0:.1f}s; records in {metrics_path}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
