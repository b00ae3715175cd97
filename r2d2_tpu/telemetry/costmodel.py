"""XLA cost-model extraction + the analytic per-component cost model
(ISSUE 9 tentpole).

Two complementary views of "where does a step's compute go", one
machine-readable table for both:

  * **XLA program costs** (``collect_cost_table``): every compiled step
    factory — the learner step (single / multi-step scan / dp-sharded
    shard_map / GSPMD-TP external-batch), ``replay_add_many``,
    ``replay_sample``, and the anakin acting program — lowered AOT from
    shape avals and read back through ``compiled.cost_analysis()`` /
    ``memory_analysis()``: flops, transcendentals, bytes accessed,
    output bytes, argument/output/temp buffer sizes. Works on the CPU
    backend (tier-1-testable) and on TPU identically.
  * **Analytic component model** (``analytic_component_costs``): the
    PERF.md roofline's hand math as code — per-component
    (torso / lstm / head / sum_tree / replay) FLOPs and bytes per train
    step from the config alone, plus the serial-chain model. The
    program totals calibrate it; the component split is what the
    roofline report (tools/roofline.py) and the periodic record's
    ``costs`` block are built from.

THE while-loop caveat (XLA HloCostAnalysis; first measured under jax
0.4.37, and the unroll-twin parity test below still holds under 0.9.0): a
``while`` body is counted ONCE, not x trip-count — so any ``lax.scan``
program (the LSTM time scan, the multi-step dispatch scan, the anakin
acting scan) undercounts its loop body's flops by (T-1)/T. Two uses,
two treatments:

  * the **regression gate** (``make regress`` via tools/regress.py)
    compares tables compiled exactly like production (scan form) with
    exact-match tolerance: analytic counts are deterministic, and any
    real change to the loop body still shifts the counted body cost, so
    an injected 2x FLOP change fails the gate even though the absolute
    number under-represents executed work;
  * the **roofline** compiles an *unroll twin* (``unroll_scans=True``:
    ``network.scan_unroll = seq_len`` and the anakin scan's ``unroll =
    block_length``) so the counted flops reflect executed work — that
    twin is what parity against ``bench.model_flops_per_step`` is
    asserted on (within 5%; tests/test_costmodel.py).

CLI (the ``make costs`` face):

    python -m r2d2_tpu.telemetry.costmodel --out COSTS.json
"""

import dataclasses
import json
import sys
from typing import Any, Dict, Iterable, Optional, Tuple

# ---------------------------------------------------------------------------
# THE peak table (roofline denominators), keyed by the exact
# ``jax.devices()[0].device_kind`` string. One row per chip the repo has
# run on; a device that is not here is an error, not a default.
# ---------------------------------------------------------------------------

PEAK_SPECS: Dict[str, Dict[str, float]] = {
    # TPU v5e, per chip — Google Cloud documentation, "TPU v5e": 197
    # TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. f32 taken as
    # half the bf16 rate. "TPU v5 lite" is what the chip reports
    # (chip_smoke.py prints it).
    "TPU v5 lite": dict(flops_bf16=197e12, flops_int8=393e12,
                        flops_f32=98.5e12, hbm_gbps=819.0),
}

# nominal 2-core-container numbers, NOT a measurement — %-of-peak rows on
# the CPU backend are structural smoke for the test suite, never quoted
# (nominal=True rides the report so a reader cannot mistake them)
CPU_NOMINAL = dict(flops_bf16=5e10, flops_f32=5e10, hbm_gbps=10.0)


def peak_spec(device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Peak FLOP/s + HBM bandwidth for a device kind (default: device 0
    of the current backend). The CPU gets the flagged nominal row; any
    other device missing from ``PEAK_SPECS`` raises."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind in PEAK_SPECS:
        return dict(PEAK_SPECS[device_kind], device_kind=device_kind,
                    nominal=False)
    if device_kind.lower() == "cpu":
        return dict(CPU_NOMINAL, device_kind=device_kind, nominal=True)
    raise KeyError(
        f"no peak figures for device_kind {device_kind!r}: add its "
        "published per-chip peaks, with their source, to "
        "telemetry/costmodel.py PEAK_SPECS (known: "
        f"{sorted(PEAK_SPECS)})")


# ---------------------------------------------------------------------------
# analytic component model
# ---------------------------------------------------------------------------

COMPONENTS = ("torso", "lstm", "head", "sum_tree", "replay")


def _conv_pyramid(cfg, action_dim: int):
    """Per-layer conv MACs/token + activation element counts, plus the
    downstream FC/LSTM/head MACs — the one place the per-token shape
    math lives (bench.model_flops_per_step delegates here)."""
    net, env = cfg.network, cfg.env
    h, w, c = env.frame_height, env.frame_width, env.frame_stack
    conv_macs, conv_elems = [], []
    for features, kernel, stride in net.conv_layers:
        h = (h - kernel) // stride + 1
        w = (w - kernel) // stride + 1
        conv_macs.append(h * w * features * kernel * kernel * c)
        conv_elems.append(h * w * features)
        c = features
    fc_macs = h * w * c * net.cnn_out_dim
    lstm_in = net.cnn_out_dim + action_dim
    lstm_macs = 4 * net.hidden_dim * (lstm_in + net.hidden_dim)
    head_macs = net.hidden_dim * net.hidden_dim + net.hidden_dim * action_dim
    if net.use_dueling:
        head_macs += net.hidden_dim * net.hidden_dim + net.hidden_dim
    return conv_macs, conv_elems, fc_macs, lstm_macs, head_macs


def model_flops_per_step(cfg, action_dim: int, use_double: bool) -> float:
    """Analytic model FLOPs for one train step: fwd + bwd (~2x fwd) +
    the target fwd when double-DQN is on, counting conv/FC/LSTM/head
    matmul MACs over the full (batch x seq_window) unroll at 2 FLOPs per
    MAC. Elementwise/decode/Adam FLOPs are noise against these and are
    not counted.

    Reconciled against XLA's ``cost_analysis()`` (ISSUE 9 satellite;
    parity-tested within 5% in tests/test_costmodel.py): the FIRST
    conv's input gradient is never computed — the observation needs no
    grad, XLA DCEs that backward conv — so the first conv contributes
    one unroll fewer than every other matmul. The pre-PR9 count skipped
    that term and overcounted 5-7% at the reference shape (the
    PERF.md:383 slope-sanity drift)."""
    conv_macs, _, fc_macs, lstm_macs, head_macs = _conv_pyramid(
        cfg, action_dim)
    unrolls = 3.0 + (1.0 if use_double else 0.0)
    tokens = cfg.replay.batch_size * cfg.sequence.seq_len
    macs_all = sum(conv_macs) + fc_macs + lstm_macs + head_macs
    # first conv: fwd + weight-grad + (target fwd), NO input-grad (a
    # conv-less torso has no such term)
    first_conv = conv_macs[0] if conv_macs else 0.0
    return 2.0 * tokens * (macs_all * unrolls - first_conv)


def analytic_component_costs(cfg, action_dim: int,
                             use_double: Optional[bool] = None,
                             act_bytes: Optional[int] = None
                             ) -> Dict[str, Any]:
    """Per-component FLOPs and bytes for ONE train step, from the config
    alone — pure math, no compile, deterministic (the periodic record's
    ``costs`` block and the roofline's component split).

    Bytes are documented first-order estimates: activations read+written
    once per unroll in the compute dtype, parameters read once per
    unroll in f32, the uint8 obs gather + decode, and the sum-tree's
    node touches — accurate enough to classify compute- vs memory-bound
    per component, NOT a byte-exact transfer model (the XLA program
    totals are; see ``collect_cost_table``).

    ``act_bytes`` is the activation dtype size: callers holding the
    RESOLVED compute dtype (the roofline tool, the Learner's record
    block — NetworkApply resolves the bf16 tri-state) pass 2 or 4 so
    the byte counts match the peak row they'll be judged against;
    unresolved contexts default to the backend-independent f32 worst
    case ("auto" counted as 4 — the golden-file convention)."""
    net, env, seq = cfg.network, cfg.env, cfg.sequence
    if use_double is None:
        use_double = net.use_double
    conv_macs, conv_elems, fc_macs, lstm_macs, head_macs = _conv_pyramid(
        cfg, action_dim)
    B, T = cfg.replay.batch_size, seq.seq_len
    tokens = B * T
    unrolls = 3.0 + (1.0 if use_double else 0.0)
    if act_bytes is None:
        act_bytes = 2 if str(net.bf16).lower() in ("on", "true", "1") else 4
    H = net.hidden_dim

    obs_bytes = tokens * env.frame_height * env.frame_width * env.frame_stack
    conv_act_bytes = sum(conv_elems) * tokens * act_bytes
    # f32 parameter bytes per component (kernels + FC / gates / heads)
    c_in = env.frame_stack
    torso_params = 0.0
    for features, kernel, _ in net.conv_layers:
        torso_params += 4.0 * kernel * kernel * c_in * features
        c_in = features
    fc_in = conv_elems[-1] if conv_elems else 0
    torso_params += 4.0 * fc_in * net.cnn_out_dim
    lstm_params = 4.0 * 4 * H * ((net.cnn_out_dim + action_dim) + H)
    head_params = 4.0 * head_macs

    components = {
        "torso": {
            # first conv contributes one unroll fewer (no input grad)
            "flops": 2.0 * tokens * (
                (sum(conv_macs) + fc_macs) * unrolls
                - (conv_macs[0] if conv_macs else 0.0)),
            "bytes": (obs_bytes              # uint8 frame gather
                      + obs_bytes * act_bytes  # decoded stack write
                      + 2.0 * unrolls * conv_act_bytes
                      + unrolls * torso_params),
        },
        "lstm": {
            "flops": 2.0 * tokens * lstm_macs * unrolls,
            # hoisted input projection activations + the per-step h/c
            # chain; recurrent weights counted once (VMEM-resident
            # across the scan — the fused-kernel design assumption)
            "bytes": (2.0 * unrolls * tokens * 4 * H * act_bytes
                      + 2.0 * unrolls * tokens * 2 * H * act_bytes
                      + unrolls * lstm_params),
        },
        "head": {
            "flops": 2.0 * tokens * head_macs * unrolls,
            "bytes": (2.0 * unrolls * tokens * (H + action_dim) * act_bytes
                      + unrolls * head_params),
        },
    }
    # prioritized sum tree: stratified descent (sample) + leaf update +
    # bottom-up rebuild — a handful of f32 ops per (sample x layer)
    from r2d2_tpu.ops.sum_tree import tree_num_layers
    layers = tree_num_layers(cfg.num_sequences)
    sum_tree_touches = B * layers
    components["sum_tree"] = {
        "flops": 8.0 * sum_tree_touches,          # cmp/sub/add per level x2 passes
        "bytes": 4.0 * 4 * sum_tree_touches,      # 2 reads + write, f32, x2 passes
    }
    # replay-side data movement of one sample: the uint8 window gather out
    # of the ring + hidden/meta rows (flops-free, pure bytes)
    components["replay"] = {
        "flops": 0.0,
        "bytes": float(obs_bytes + B * 2 * H * 4
                       + B * seq.learning_steps * 4 * 4),
    }

    total_flops = sum(c["flops"] for c in components.values())
    # the serial recurrent chain (PERF.md round-5 model): fwd + bwd
    # always walk the chain; the target fwd adds a third under double-DQN
    serial_walks = 3 if use_double else 2
    serial_iters = T * serial_walks
    serial_flops = 2.0 * 4 * H * H * B * serial_iters
    return {
        "components": components,
        "total_flops": total_flops,
        "model_flops_per_step": model_flops_per_step(cfg, action_dim,
                                                     use_double),
        "tokens_per_step": tokens,
        "unrolls": unrolls,
        "serial_chain": {
            "iterations": serial_iters,
            "per_iter_flops": 2.0 * 4 * H * H * B,
            "flops": serial_flops,
            "share_of_total": (serial_flops / total_flops
                               if total_flops else 0.0),
        },
    }


# ---------------------------------------------------------------------------
# XLA program-cost extraction
# ---------------------------------------------------------------------------


def _sds(tree):
    """ShapeDtypeStruct twin of a pytree, preserving shardings where the
    leaves carry them (committed arrays of a sharded replay/state —
    lowering a shard_map program from unsharded avals would let the
    compiler pick layouts the real arrays don't match)."""
    import jax

    def one(x):
        sharding = getattr(x, "sharding", None)
        try:
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        except TypeError:
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

    return jax.tree_util.tree_map(one, tree)


def program_cost(compiled) -> Dict[str, Any]:
    """Flatten one compiled executable's ``cost_analysis()`` +
    ``memory_analysis()`` into a plain dict. Tolerant of backend
    variance: either API may be absent/None on exotic backends — missing
    numbers are simply omitted, never fabricated."""
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:                               # pragma: no cover
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        for key, name in (("flops", "flops"),
                          ("transcendentals", "transcendentals"),
                          ("bytes accessed", "bytes_accessed"),
                          ("bytes accessedout{}", "output_bytes_accessed")):
            if key in ca:
                out[name] = float(ca[key])
    try:
        ma = compiled.memory_analysis()
    except Exception:                               # pragma: no cover
        ma = None
    if ma is not None:
        for attr, name in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes")):
            v = getattr(ma, attr, None)
            if v is not None:
                out[name] = int(v)
    return out


def _cost_of(jitted, *args) -> Dict[str, Any]:
    return program_cost(jitted.lower(*args).compile())


GATE_VARIANTS = ("learner_step", "learner_step_multi", "learner_step_sharded",
                 "learner_step_tp", "replay_add_many", "replay_sample",
                 "anakin_act", "serve_forward", "quant_forward")


def collect_cost_table(cfg, variants: Iterable[str] = GATE_VARIANTS,
                       unroll_scans: bool = False) -> Dict[str, Any]:
    """Lower + compile each requested step factory at ``cfg``'s shapes
    and extract its program costs into one machine-readable table.

    ``unroll_scans`` builds the roofline's unroll twin (scan bodies
    fully unrolled so flops count executed work — see module caveat);
    the default scan form is what the regression gate snapshots. Every
    program is built with ``diag=None`` (the telemetry kill-switch
    baseline program).

    Variants needing a wider mesh than the backend offers raise — the
    gate must be deterministic, so "silently skipped" is not a state.
    """
    import jax

    from r2d2_tpu.envs.factory import create_jax_env
    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_external_batch_step,
                                             make_learner_step,
                                             make_multi_learner_step)
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.replay.device_replay import (replay_add_many, replay_init,
                                               replay_sample)
    from r2d2_tpu.replay.structs import ReplaySpec
    from r2d2_tpu.replay.synthetic import make_synthetic_block

    variants = tuple(variants)
    if unroll_scans:
        cfg = cfg.replace(**{"network.scan_unroll": cfg.sequence.seq_len})
    env = create_jax_env(cfg.env)
    action_dim = env.action_dim
    spec = ReplaySpec.from_config(cfg)
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    ts_aval = _sds(jax.eval_shape(
        lambda k: create_train_state(k, net, cfg.optim),
        jax.random.PRNGKey(0)))
    rs_aval = _sds(jax.eval_shape(lambda: replay_init(spec)))
    key_aval = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)

    programs: Dict[str, Dict[str, Any]] = {}

    if "learner_step" in variants:
        step = make_learner_step(net, spec, cfg.optim,
                                 cfg.network.use_double)
        programs["learner_step"] = _cost_of(step, ts_aval, rs_aval)
    if "learner_step_multi" in variants:
        k = max(cfg.runtime.steps_per_dispatch, 2)
        multi = make_multi_learner_step(net, spec, cfg.optim,
                                        cfg.network.use_double, k)
        programs["learner_step_multi"] = dict(
            _cost_of(multi, ts_aval, rs_aval), steps_per_dispatch=k)
    if "learner_step_sharded" in variants or "learner_step_tp" in variants:
        from r2d2_tpu.parallel import make_mesh
    if "learner_step_sharded" in variants:
        from r2d2_tpu.parallel import make_sharded_learner_step
        from r2d2_tpu.parallel.mesh import dp_sharding
        dp = max(cfg.mesh.dp, 2)
        if len(jax.devices()) < dp:
            raise RuntimeError(
                f"learner_step_sharded needs {dp} devices, backend has "
                f"{len(jax.devices())} — pin a virtual mesh first "
                "(utils.platform.pin_cpu_platform)")
        mesh = make_mesh(dataclasses.replace(cfg.mesh, dp=dp, mp=1))
        sharded = make_sharded_learner_step(
            net, spec, cfg.optim, cfg.network.use_double, mesh,
            steps_per_dispatch=1)
        # avals only — materializing the real sharded ring just to read
        # shardings would allocate the multi-GiB obs buffers at the
        # reference shape; sharded_replay_init's layout is uniform
        # (leading dp axis, every leaf dp_sharding-placed), so build it
        sharding = dp_sharding(mesh)
        srs_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((dp,) + a.shape, a.dtype,
                                           sharding=sharding),
            jax.eval_shape(lambda: replay_init(spec)))
        programs["learner_step_sharded"] = dict(
            _cost_of(sharded, ts_aval, srs_aval), dp=dp)
    if "learner_step_tp" in variants:
        from r2d2_tpu.parallel.tensor_parallel import (
            make_tp_external_batch_step, state_shardings)
        mp = max(cfg.mesh.mp, 2)
        if len(jax.devices()) < mp:
            raise RuntimeError(
                f"learner_step_tp needs {mp} devices, backend has "
                f"{len(jax.devices())}")
        from jax.sharding import NamedSharding, PartitionSpec as P
        tp_mesh = make_mesh(dataclasses.replace(cfg.mesh, dp=1, mp=mp))
        tp_step, _, _ = make_tp_external_batch_step(
            net, spec, cfg.optim, cfg.network.use_double, tp_mesh)
        shardings = state_shardings(
            jax.eval_shape(lambda k: create_train_state(k, net, cfg.optim),
                           jax.random.PRNGKey(0)), tp_mesh)
        ts_tp = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            ts_aval, shardings)
        batch_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(tp_mesh, P("dp"))),
            jax.eval_shape(lambda r, k: replay_sample(spec, r, k),
                           rs_aval, key_aval))
        programs["learner_step_tp"] = dict(
            _cost_of(tp_step, ts_tp, batch_aval), mp=mp)
    if "external_batch_step" in variants:
        ext = make_external_batch_step(net, spec, cfg.optim,
                                       cfg.network.use_double)
        batch_aval = _sds(jax.eval_shape(
            lambda r, k: replay_sample(spec, r, k), rs_aval, key_aval))
        programs["external_batch_step"] = _cost_of(ext, ts_aval, batch_aval)
    if "replay_add_many" in variants:
        import numpy as np
        k = min(8, spec.num_blocks)
        blk = make_synthetic_block(spec, np.random.default_rng(0))
        blocks_aval = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((k,) + np.shape(x),
                                           np.asarray(x).dtype), blk)
        add = jax.jit(lambda s, b: replay_add_many(spec, s, b),
                      donate_argnums=0)
        programs["replay_add_many"] = dict(
            _cost_of(add, rs_aval, blocks_aval), blocks=k)
    if "replay_sample" in variants:
        samp = jax.jit(lambda s, k: replay_sample(spec, s, k))
        programs["replay_sample"] = _cost_of(samp, rs_aval, key_aval)
    if "serve_forward" in variants or "quant_forward" in variants:
        h_f, w_f = cfg.env.frame_height, cfg.env.frame_width
        s_f, hd_f = cfg.env.frame_stack, cfg.network.hidden_dim
        params_aval = _sds(jax.eval_shape(net.init, jax.random.PRNGKey(0)))

        def fwd_avals(b):
            return (jax.ShapeDtypeStruct((b, h_f, w_f, s_f),
                                         jax.numpy.float32),
                    jax.ShapeDtypeStruct((b,), jax.numpy.int32),
                    jax.ShapeDtypeStruct((b, 2, hd_f), jax.numpy.float32))
    if "serve_forward" in variants:
        # the serving plane's pow2 dispatch buckets (ISSUE 14 satellite:
        # PR 12 added the micro-batched program but never tabled it) —
        # one row per AOT-precompiled bucket of the PRODUCTION serve
        # forward at this config's inference dtype, so `make costs` /
        # tools/roofline.py cover the serving plane and the costs gate
        # catches a program change at any width
        from r2d2_tpu.actor.policy import make_forward_fn
        from r2d2_tpu.serve.server import serve_buckets
        fwd = make_forward_fn(
            net, probe_interval=(cfg.telemetry.quant_probe_interval
                                 if cfg.network.inference_dtype != "f32"
                                 else 0))
        quant_mode = cfg.network.inference_dtype != "f32"
        if quant_mode:
            from r2d2_tpu.models.network import make_inference_bundle
            serve_params = _sds(jax.eval_shape(
                lambda p: make_inference_bundle(net, p, 0), params_aval))
        else:
            serve_params = params_aval
        for b in serve_buckets(cfg.serve.max_batch):
            args = (serve_params,) + fwd_avals(b)
            if quant_mode:
                # + tick and live-row count (the quant signature)
                args = args + (jax.ShapeDtypeStruct((), jax.numpy.int32),
                               jax.ShapeDtypeStruct((), jax.numpy.int32))
            programs[f"serve_forward_b{b}"] = dict(_cost_of(fwd, *args),
                                                   batch=b)
    if "quant_forward" in variants:
        # the quantized-acting weight-streaming rows (ISSUE 14): the
        # probe-free forward over EXACTLY the weight tree the steady
        # state streams per dispatch — f32 params vs the bf16/int8
        # twins — plus the analytic weight_bytes each one reads. The
        # int8 row's weight_bytes / the f32 row's is the >= 3x cut the
        # TPU projection rests on; both are exact-match-gated.
        from r2d2_tpu.models.network import (param_tree_bytes,
                                             quantize_params,
                                             quantized_inference_apply)
        bq = cfg.serve.max_batch
        for mode in ("f32", "bf16", "int8"):
            if mode == "f32":
                from r2d2_tpu.actor.policy import make_forward_fn
                fn = make_forward_fn(net, "f32")
                tree_aval = params_aval
            else:
                net_m = NetworkApply(
                    action_dim,
                    dataclasses.replace(cfg.network, inference_dtype=mode),
                    cfg.env.frame_stack, cfg.env.frame_height,
                    cfg.env.frame_width)

                def step(qt, stacked, last_action, hidden, _net=net_m):
                    import jax.numpy as jnp
                    obs = stacked[:, None]
                    la = jax.nn.one_hot(last_action, _net.action_dim,
                                        dtype=jnp.float32)[:, None]
                    q, h2 = quantized_inference_apply(_net, qt, obs, la,
                                                      hidden)
                    return jnp.argmax(q[:, 0], axis=-1), q[:, 0], h2

                fn = jax.jit(step)
                tree_aval = _sds(jax.eval_shape(
                    lambda p, _m=mode: quantize_params(p, _m), params_aval))
            programs[f"acting_forward_{mode}"] = dict(
                _cost_of(fn, tree_aval, *fwd_avals(bq)), batch=bq,
                weight_bytes=param_tree_bytes(tree_aval))
    if "anakin_act" in variants:
        from r2d2_tpu.actor.anakin import init_act_carry, make_anakin_act
        from r2d2_tpu.config import apex_epsilon
        lanes = cfg.actor.anakin_lanes
        eps = [apex_epsilon(i, lanes, cfg.actor.base_eps,
                            cfg.actor.eps_alpha) for i in range(lanes)]
        act = make_anakin_act(
            env, net, spec, num_lanes=lanes, epsilons=eps,
            gamma=cfg.optim.gamma, priority=cfg.actor.anakin_priority,
            near_greedy_eps=cfg.actor.near_greedy_eps,
            priority_eta=cfg.optim.priority_eta,
            unroll=spec.block_length if unroll_scans else 1)
        carry_aval = _sds(jax.eval_shape(
            lambda k: init_act_carry(env, spec, lanes, k),
            jax.random.PRNGKey(0)))
        wv_aval = jax.ShapeDtypeStruct((), jax.numpy.int32)
        act_params_aval = ts_aval.params
        if cfg.network.inference_dtype != "f32":
            # the quantized acting scan takes the published inference
            # bundle, not raw params (actor/anakin.py)
            from r2d2_tpu.models.network import make_inference_bundle
            act_params_aval = _sds(jax.eval_shape(
                lambda p: make_inference_bundle(net, p, 0),
                ts_aval.params))
        programs["anakin_act"] = dict(
            _cost_of(act, act_params_aval, carry_aval, wv_aval),
            lanes=lanes)

    return {
        "schema": 1,
        "backend": jax.default_backend(),
        "unroll_scans": bool(unroll_scans),
        "action_dim": action_dim,
        "shape": {
            "batch_size": spec.batch_size,
            "seq_len": cfg.sequence.seq_len,
            "frame": [cfg.env.frame_height, cfg.env.frame_width,
                      cfg.env.frame_stack],
            "hidden_dim": cfg.network.hidden_dim,
            "block_length": spec.block_length,
            "use_double": bool(cfg.network.use_double),
        },
        "programs": programs,
    }


# ---------------------------------------------------------------------------
# the regression-gate fixture: ONE pinned tiny config (compiles in
# seconds on the CPU backend) whose table BASELINE.json snapshots under
# "costs" — tools/regress.py recomputes and exact-compares it, so a
# refactor that silently changes any step factory's flops/bytes fails
# `make regress` even on wall-clock-noisy hosts.
# ---------------------------------------------------------------------------

GATE_OVERRIDES = {
    "env.game_name": "Fake",
    "env.frame_height": 24, "env.frame_width": 24, "env.frame_stack": 2,
    "env.episode_len": 40,
    "network.conv_layers": ((8, 4, 2), (16, 3, 1)),
    "network.hidden_dim": 32, "network.cnn_out_dim": 64,
    "network.use_double": True,
    "sequence.burn_in_steps": 6, "sequence.learning_steps": 5,
    "sequence.forward_steps": 3,
    "replay.capacity": 800, "replay.block_length": 20,
    "replay.batch_size": 8, "replay.learning_starts": 100,
    "actor.anakin_lanes": 4,
    "runtime.steps_per_dispatch": 3,
}


def gate_config():
    from r2d2_tpu.config import Config
    return Config().replace(**GATE_OVERRIDES)


_gate_table_cache: Optional[Dict[str, Any]] = None


def gate_table() -> Dict[str, Any]:
    """The gated cost table: the pinned fixture config through every
    step-factory variant, in production (scan) form. Deterministic for a
    given jax/XLA build + backend; `make regress` runs it CPU-pinned.
    Memoized per process — the ~20-30 s of tiny-config compiles are a
    pure function of the checked-out code, and the regress-gate tests
    drive the CLI's main() several times in one process."""
    global _gate_table_cache
    if _gate_table_cache is None:
        _gate_table_cache = collect_cost_table(
            gate_config(), variants=GATE_VARIANTS, unroll_scans=False)
    return _gate_table_cache


def compare_cost_tables(baseline: Dict[str, Any], current: Dict[str, Any],
                        rtol: float = 1e-6) -> list:
    """One row per baselined program metric: ok / CHANGED / missing.
    Unlike the bench gate's lower-is-worse tolerance bands, ANY relative
    change beyond ``rtol`` fails in BOTH directions — the analytic
    counts are deterministic, and a silent 2x FLOP increase is exactly
    the regression this gate exists for. Programs new in ``current``
    are not rows (they join at the next --update)."""
    rows = []
    base_progs = (baseline or {}).get("programs") or {}
    cur_progs = (current or {}).get("programs") or {}
    for prog, metrics in sorted(base_progs.items()):
        cur = cur_progs.get(prog)
        for name, base in sorted(metrics.items()):
            if not isinstance(base, (int, float)) or isinstance(base, bool):
                continue
            row = {"program": prog, "metric": name, "baseline": float(base)}
            if cur is None or name not in cur:
                row.update({"current": None, "status": "missing"})
            else:
                value = float(cur[name])
                row["current"] = value
                denom = max(abs(float(base)), 1.0)
                if abs(value - float(base)) / denom > rtol:
                    row["status"] = "CHANGED"
                    row["delta_pct"] = round(
                        100.0 * (value - float(base)) / denom, 3)
                else:
                    row["status"] = "ok"
            rows.append(row)
    return rows


def main(argv=None) -> int:
    import argparse

    from r2d2_tpu.utils.platform import pin_cpu_platform
    p = argparse.ArgumentParser(
        description="extract the per-program XLA cost table (make costs)")
    p.add_argument("--out", default="COSTS.json")
    p.add_argument("--unroll-scans", action="store_true",
                   help="build the roofline's unroll twin instead of the "
                        "gate's scan-form table")
    p.add_argument("--variants", nargs="*", default=None,
                   help=f"subset of {GATE_VARIANTS}")
    p.add_argument("--reference-shape", action="store_true",
                   help="use the full reference config instead of the "
                        "pinned gate fixture (slow compiles)")
    args = p.parse_args(argv)

    # the sharded variant needs >= 2 devices; a virtual CPU mesh keeps
    # the table backend-independent and tier-1-testable
    pin_cpu_platform(2)
    from r2d2_tpu.config import Config
    cfg = Config() if args.reference_shape else gate_config()
    table = collect_cost_table(cfg, variants=args.variants or GATE_VARIANTS,
                               unroll_scans=args.unroll_scans)
    with open(args.out, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    for prog, m in sorted(table["programs"].items()):
        print(f"{prog:>22}: flops={m.get('flops', 0):.6g} "
              f"bytes={m.get('bytes_accessed', 0):.6g} "
              f"temp={m.get('temp_bytes', 0):.4g}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
