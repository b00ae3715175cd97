"""Spawned actor process entry point.

Kept import-light on purpose: with the ``spawn`` start method the child
re-imports this module before unpickling the target function. The TPU
belongs to the learner process alone (the reference gets this isolation for
free from Ray's per-actor processes + CUDA_VISIBLE_DEVICES,
/root/reference/config.py:1); ``actor_process_main`` pins the child to the
host CPU before it touches a backend.
"""

import os


def actor_process_main(cfg_dict: dict, player_idx: int, actor_idx: int,
                       epsilon: float, shm_name: str, queue, stop_event,
                       is_host: bool, port: int,
                       total_actors: int = None,
                       health_board=None, health_slot: int = None,
                       telemetry_board=None, serve_spec: dict = None,
                       generation: int = 0) -> None:
    # total_actors: the GLOBAL worker-fleet size for the vector ε ladder —
    # multihost spawners pass process_count * num_actors with a global
    # actor_idx; None = single-host (cfg.actor.num_actors)
    # A respawn dispatched just before shutdown can finish booting AFTER
    # the parent unlinked the weight/heartbeat segments — exit quietly
    # instead of dying loudly on a FileNotFoundError mid-bring-up.
    if stop_event.is_set():
        return
    # One process per chip: the TPU belongs to the learner, so the child is
    # pinned to the CPU unconditionally (not setdefault — an inherited
    # JAX_PLATFORMS naming the TPU would have every child race the parent
    # for libtpu). The env var alone arrives too late: under spawn this
    # child already re-imported the parent's main module, and with it jax,
    # before this function ran — pin_platform()'s jax.config.update is what
    # actually keeps the child off the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from r2d2_tpu.utils import pin_platform
    pin_platform()
    import jax
    import numpy as np

    from r2d2_tpu.config import Config
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.actor_loop import make_actor_env, make_actor_policy
    from r2d2_tpu.runtime.weights import WeightSubscriber

    cfg = Config.from_dict(cfg_dict)
    seed = cfg.runtime.seed + 10_000 * player_idx + 100 * actor_idx
    # scalar or vectorized per cfg.actor.envs_per_actor — the shared
    # construction path (actor_loop.py) picks for env and policy alike
    env = make_actor_env(cfg, player_idx, actor_idx, seed,
                         is_host=is_host, port=port,
                         num_players=cfg.multiplayer.num_players)
    net = NetworkApply(env.action_space.n, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    sub = None
    serve_channel = None
    if cfg.actor.inference == "server" and serve_spec is not None:
        # served inference (ISSUE 13): this worker is a THIN client — no
        # local params, no weight subscriber. The channel rides the rung
        # the parent picked: the shm request ring handle crossed the
        # spawn boundary by name; socket just dials.
        params = None
        if serve_spec["transport"] == "shm":
            from r2d2_tpu.serve import ShmServeChannel
            serve_channel = ShmServeChannel(
                serve_spec["request_ring"], serve_spec["action_dim"],
                serve_spec["hidden_dim"],
                reply_slots=serve_spec["reply_slots"])
        elif serve_spec["transport"] == "socket_fleet":
            # sharded serving (ISSUE 17): one socket per fleet server,
            # routed client-id → shard → server off the shipped
            # assignment; MISROUTED bounces re-aim as the fleet churns
            from r2d2_tpu.serve import (RoutingChannel, ShardMap,
                                        SocketChannel)
            version, assign = serve_spec["assign"]
            smap = ShardMap(serve_spec["total_shards"], assign)
            smap.version = int(version)
            # eager dial on the bounded ladder (ISSUE 18): a server that
            # is still binding is retried with backoff; a misaddressed
            # one raises HERE with the real ECONNREFUSED instead of a
            # timeout storm at the first request
            serve_channel = RoutingChannel(
                {slot: SocketChannel(host, port, connect_retries=5,
                                     eager_connect=True)
                 for slot, (host, port) in serve_spec["servers"].items()},
                smap)
        else:
            from r2d2_tpu.serve import SocketChannel
            serve_channel = SocketChannel(serve_spec["host"],
                                          serve_spec["port"],
                                          connect_retries=5,
                                          eager_connect=True)
    else:
        params = net.init(jax.random.PRNGKey(cfg.runtime.seed))
        # quantized inference (ISSUE 14): the published tree is the
        # inference bundle — the subscriber template must match its
        # structure (a locally-quantized twin of the init params; the
        # policy swaps it for the learner's published twin on first poll)
        from r2d2_tpu.runtime.weights import make_publish_preparer
        prep = make_publish_preparer(net)
        if prep is not None:
            params = jax.device_get(prep(params, 0))
        try:
            sub = WeightSubscriber(shm_name, params)
        except FileNotFoundError:
            if stop_event.is_set():
                env.close()  # parent tore the segments down mid-boot
                return
            raise
        fresh = sub.poll()
        if fresh is not None:
            params = fresh
    # copy_updates=False: WeightSubscriber.poll materializes a fresh copy
    # per poll already — the policy may own those buffers directly
    policy, run_loop = make_actor_policy(cfg, net, params, actor_idx, seed,
                                         epsilon=epsilon,
                                         copy_updates=False,
                                         total_actors=total_actors,
                                         serve_channel=serve_channel,
                                         should_stop=stop_event.is_set)

    from r2d2_tpu.runtime.actor_loop import instrument_block_sink
    from r2d2_tpu.runtime.feeder import put_patient

    # health wiring: heartbeat per block emit + liveness touches while
    # parked under back-pressure, and fault injection for this slot —
    # same instrumentation point as the thread spawners (actor_loop.py).
    # health_slot is the fleet-local index (actor_idx is GLOBAL under a
    # multihost fleet); it defaults to actor_idx for single-host spawners.
    slot = actor_idx if health_slot is None else health_slot
    beat = ((lambda: health_board.touch(slot))
            if health_board is not None else None)

    # telemetry: this process's stage timers publish into its slot of the
    # shared board (the learner aggregates per log interval); spans drain
    # to a per-process JSONL next to the training logs. The board handle
    # crossed the spawn boundary by name, same lifecycle as the
    # heartbeat board.
    from r2d2_tpu.telemetry import Telemetry
    tele = Telemetry.from_config(
        cfg, name=f"actor-p{player_idx}-{actor_idx}",
        board=telemetry_board, slot=slot)
    if tele.enabled:
        # append: a supervisor respawn must not wipe the previous
        # incarnation's spans — the crash window is exactly what a
        # post-mortem trace export wants (the spawner truncates stale
        # files once per fresh run)
        tele.start_drain(os.path.join(
            cfg.runtime.save_dir or ".",
            f"spans_p{player_idx}_a{actor_idx}.jsonl"), append=True)

    sink = instrument_block_sink(
        cfg, slot,
        lambda b: put_patient(queue, b, stop_event.is_set, beat=beat,
                              telemetry=tele),
        board=health_board, telemetry=tele,
        # staleness stamp: the publish count of the params this actor is
        # acting with — the subscriber's last adopted version locally,
        # or (served) the server's adopted count riding each reply
        weight_version=((lambda: policy.weight_version)
                        if sub is None else (lambda: sub.publish_count)),
        # lane provenance (ISSUE 10): actor_idx is the GLOBAL worker
        # index (multihost fleets pass theirs), matching the ladder
        # layout vector_lane_epsilons spreads ε over
        lane_base=actor_idx * cfg.actor.envs_per_actor,
        # membership generation (ISSUE 15): an adopted slot's joiner
        # (generation > 0) must not inherit the slot's 'leave' fault
        generation=generation)

    from r2d2_tpu.tools.chaos import ChaosLeave
    try:
        run_loop(cfg, env, policy,
                 block_sink=sink,
                 weight_poll=(sub.poll if sub is not None
                              else (lambda: None)),
                 should_stop=stop_event.is_set,
                 telemetry=tele)
    except ChaosLeave:
        # deliberate departure (ISSUE 15 leave@block=N): exit 0 — the
        # elastic supervisor parks the slot for re-adoption; a loud
        # nonzero exit here would read as a crash in the logs
        pass
    except Exception:
        if not stop_event.is_set():
            raise      # a served policy raising at shutdown is clean-stop
    finally:
        tele.close()
        if sub is not None:
            sub.close()
        if serve_channel is not None:
            policy.close()
        # env is closed by the run loop (its finally owns it)
