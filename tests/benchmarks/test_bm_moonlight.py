"""The ``moonlight-core`` configuration: the program's ``mla_moe`` core
against the plain reference (forward, loss and gradients, float32 and bf16,
through ``check.compare``), the chip's share of the experts against the
uncut layer, routing under a skewed router, the training record's counters,
the benchmark's count of operations against XLA's, and the files' contract
with the catalog. Tiny sizes, CPU, seeded random weights."""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_structure
from benchmarks import costs, costs_mla_moe, harness, run, traffic
from benchmarks.reference import check, r2d2_mla_moe
from benchmarks.runners import learner_long

BENCH = harness.load_benchmark()
CELL = bm_structure.MOONLIGHT
CONFIG = harness.config_doc(BENCH, "moonlight-core")
POOL = {"pool_blocks": 4, "priority_range": [0.1, 2.0], "reward_scale": 1.0}
ACTION_DIM = 6
NEW_READERS = list(bm_structure.MOONLIGHT_READERS)


def _tiny_learner(tmp_path, seed=0, **extra):
    """The configuration's tiny CPU twin on a full ring, a target net that
    differs from the online one, and a correction bias that is not zero."""
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner

    overrides = harness.program_overrides(CONFIG, {}, rehearse=True)
    cfg = harness.build_config({**overrides, "runtime.save_interval": 0,
                                "runtime.steps_per_dispatch": 2, **extra},
                               str(tmp_path), seed)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    learner = Learner(cfg, net, 0, seed=seed)
    traffic.fill_ring(learner, ACTION_DIM, POOL, seed)

    def seeded_bias(params, key):
        mlp = params["params"]["mem_core"]["layers_1"]["mlp"]
        mlp["e_score_correction_bias"] = 0.3 * jax.random.normal(
            key, mlp["e_score_correction_bias"].shape)
        return params

    state = learner.train_state
    learner.train_state = state.replace(
        params=seeded_bias(jax.tree_util.tree_map(lambda x: x, state.params),
                           jax.random.PRNGKey(seed + 5)),
        target_params=seeded_bias(net.init(jax.random.PRNGKey(seed + 99)),
                                  jax.random.PRNGKey(seed + 6)))
    return learner


# float32 on both sides, one backend: they differ in the order of summation
# (and in the grouped product against the per-expert loop), about 1e-6
@pytest.mark.parametrize("double", [False, True])
def test_program_loss_matches_reference_in_float32(tmp_path, double):
    learner = _tiny_learner(tmp_path, **{"network.use_double": double})
    try:
        out = check.check_learner(learner, "r2d2_mla_moe", 8, seed=7)
    finally:
        learner.stop_background()
    assert out["compute_dtype"] == "float32" and out["tolerance"] == 5e-5
    assert out["ok"], out
    assert out["valid_steps"] > 0 and out["stable_steps"] > 0


def test_bf16_program_passes_its_tolerance_and_fails_float32s(tmp_path):
    learner = _tiny_learner(tmp_path, **{"network.bf16": "on"})
    try:
        program, reference, weights, dtype = check.program_and_reference(
            learner, "r2d2_mla_moe", 8, seed=7)
    finally:
        learner.stop_background()
    assert dtype == "bfloat16"
    assert check.compare(program, reference, weights, 5e-2)["ok"]
    assert not check.compare(program, reference, weights, 5e-5)["ok"]


def test_program_gradients_match_the_references(tmp_path):
    from r2d2_tpu.learner.train_step import make_loss_fn
    from r2d2_tpu.replay.structs import SampleBatch
    learner = _tiny_learner(tmp_path)
    try:
        cfg, net = learner.cfg, learner.net
        batch = check.sample_sequences(learner, 8, seed=3)
        spec = dataclasses.replace(learner.spec, batch_size=8)
        params, target = jax.device_get(
            (learner.train_state.params, learner.train_state.target_params))
    finally:
        learner.stop_background()
    program = jax.grad(lambda p: make_loss_fn(
        net, spec, cfg.optim, True)(p, target, batch)[0])(params)
    fields = {f.name: getattr(batch, f.name)
              for f in dataclasses.fields(SampleBatch)
              if getattr(batch, f.name) is not None}
    reference = jax.grad(lambda p: r2d2_mla_moe.from_config(cfg)(
        p, target, fields)["loss"])(params)
    flat_p = jax.tree_util.tree_leaves_with_path(program)
    flat_r = jax.tree_util.tree_leaves(reference)
    assert len(flat_p) == len(flat_r) > 30
    for (path, got), want in zip(flat_p, flat_r):
        scale = float(jnp.abs(want).max())
        if any(name in jax.tree_util.keystr(path) for name in (
                "e_score_correction_bias", "router_input_mean")):
            assert scale == 0.0 and float(jnp.abs(got).max()) == 0.0
            continue
        assert scale > 0, path
        np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


def _moe_layer(core, key, positions=40):
    """An expert layer's feed-forward half as the learner runs it (its
    norm's weight all ones), seeded parameters with a correction bias that
    is not zero, the residual stream it is given, and the normed positions
    the reference is given."""
    from r2d2_tpu.models.cores.mla_moe import MoE, rms_norm
    ones = jnp.ones((core.hidden_size,))
    x = jax.random.normal(key, (2, positions // 2, core.hidden_size))
    params = MoE(core, jnp.float32, True).init(jax.random.PRNGKey(1), x,
                                               ones)["params"]
    params["e_score_correction_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(2), (core.n_routed_experts,))

    def layer(core, params):
        out, stats = MoE(core, jnp.float32, True).apply({"params": params},
                                                        x, ones)
        return out.reshape(-1, core.hidden_size), stats

    flat = rms_norm(x, ones, core.rms_norm_eps).reshape(-1, core.hidden_size)
    return layer, params, flat


def _tiny_core(**extra):
    from r2d2_tpu.config import CoreConfig
    names = {f.name for f in dataclasses.fields(CoreConfig)}
    sizes = {k.split(".")[-1]: v for k, v in CONFIG["rehearsal"].items()
             if k.startswith("network.core.")}
    assert set(sizes) <= names
    return CoreConfig(**{"kind": "mla_moe", **sizes, **extra})


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips, one routed expert each (the tiny twin has 8): their
    routed parts, and the shared expert counted once, are the layer that
    holds all eight, which is the reference's uncut layer."""
    whole = _tiny_core(experts_held=8, expert_offset=0)
    layer, params, flat = _moe_layer(whole, jax.random.PRNGKey(0))
    uncut, stats = layer(whole, params)
    reference = r2d2_mla_moe._experts(flat, params, dataclasses.asdict(whole))
    np.testing.assert_allclose(uncut, reference, atol=2e-6)
    shared = r2d2_mla_moe._swiglu(flat, params["shared_experts"])
    total = shared
    for chip in range(8):
        share = dataclasses.replace(whole, experts_held=1, expert_offset=chip)
        mine = {**params, "experts": {
            k: v[chip:chip + 1] for k, v in params["experts"].items()}}
        total = total + (layer(share, mine)[0] - shared)
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert int(stats["chosen"].sum()) == flat.shape[0] * 2


@pytest.mark.parametrize("chunk_rows", [8, 2048])
def test_no_pair_is_dropped_when_the_router_is_skewed_onto_one_held_expert(
        monkeypatch, chunk_rows):
    """Every position picks held expert 1 (and one more): its group is as
    large as the batch, the sorted pairs span several chunks, and the layer
    still is the reference's, pair for pair."""
    from r2d2_tpu.models.cores import mla_moe
    monkeypatch.setattr(mla_moe, "CHUNK_ROWS", chunk_rows)
    core = _tiny_core()                       # holds experts 0..3 of 8
    layer, params, flat = _moe_layer(core, jax.random.PRNGKey(3))
    params["e_score_correction_bias"] = jnp.zeros(8).at[1].set(50.0)
    out, stats = layer(core, params)
    assert int(stats["chosen"][1]) == flat.shape[0]
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(
        out, r2d2_mla_moe._experts(flat, params, dataclasses.asdict(core)),
        atol=2e-6)


def test_training_record_carries_the_routing_counters(tmp_path):
    learner = _tiny_learner(tmp_path)
    try:
        before = jax.device_get(learner.train_state.params)
        for _ in range(3):
            learner.step()
        learner.flush_metrics()
        block = learner.metrics._moe
        after = jax.device_get(learner.train_state)
    finally:
        learner.stop_background()
    cfg = learner.cfg
    core = cfg.network.core
    pairs = (cfg.replay.batch_size * cfg.sequence.seq_len
             * core.num_experts_per_tok)
    assert block["steps"] == 6 and len(block["layers"]) == 1
    (layer,) = block["layers"]
    assert len(layer["chosen_hist"]) == core.n_routed_experts
    assert sum(layer["chosen_hist"]) == 6 * pairs
    assert layer["pairs_held"] == sum(layer["chosen_hist"][:4])
    assert layer["held_load_max"] >= layer["held_load_mean"] > 0
    assert 0 < layer["router_entropy"] <= np.log(core.n_routed_experts) + 1e-6
    assert layer["dropped"] == 0
    # no gradient and no rule moves the correction bias
    def bias(params):
        return params["params"]["mem_core"]["layers_1"]["mlp"][
            "e_score_correction_bias"]
    np.testing.assert_array_equal(bias(after.params), bias(before))
    assert np.abs(bias(before)).max() > 0
    # the train step stores the mean its routers centred their inputs on,
    # for acting to subtract
    def stored_mean(params):
        return params["params"]["mem_core"]["layers_1"]["mlp"][
            "router_input_mean"]
    assert np.abs(stored_mean(before)).max() == 0
    assert np.abs(stored_mean(after.params)).max() > 0
    assert np.isfinite(stored_mean(after.params)).all()


def test_count_of_operations_agrees_with_xlas():
    """``costs_mla_moe`` against XLA's own ``cost_analysis`` of the core's
    forward pass (as tests/test_costmodel.py holds the LSTM's count): a
    stack without expert layers, whose work does not depend on routing.
    XLA counts every key of the masked scores, the benchmark the ones a
    position sees, and XLA counts norms and the softmax too: within 10%."""
    from r2d2_tpu.models.cores.mla_moe import MlaMoeStack
    core = _tiny_core(hidden_size=128, num_attention_heads=4,
                      kv_lora_rank=64, qk_nope_head_dim=32,
                      qk_rope_head_dim=16, v_head_dim=32,
                      intermediate_size=512, num_hidden_layers=2,
                      first_k_dense_replace=2, memory_len=4)
    batch, window, in_dim = 8, 16, 70
    stack = MlaMoeStack(core, jnp.float32, False)
    x = jnp.zeros((batch, window, in_dim))
    state = jnp.zeros((batch, 2, 2 * 4 * 80 // 2))
    params = jax.eval_shape(stack.init, jax.random.PRNGKey(0), x, state)
    compiled = jax.jit(stack.apply).lower(params, x, state).compile()
    xla = compiled.cost_analysis()["flops"]
    parts = costs_mla_moe.core_macs_per_position(core, in_dim, window)
    assert parts["moe_experts"] == parts["moe_shared"] == 0
    ours = 2.0 * batch * window * sum(parts.values())
    assert 0.9 < xla / ours < 1.1, (xla, ours)


def test_expected_share_of_the_held_experts():
    cfg = harness.build_config(harness.program_overrides(
        CONFIG, harness.traffic_doc("learner-long")), "unused", 0)
    core = cfg.network.core
    parts = costs_mla_moe.core_macs_per_position(core, 1030, 125)
    # 4 expert layers x 0.75 pairs a position x 3 x 2048 x 1408
    assert parts["moe_experts"] == pytest.approx(4 * 0.75 * 3 * 2048 * 1408)
    assert parts["dense_mlp"] == 3 * 2048 * 11264
    assert parts["moe_shared"] == 4 * 3 * 2048 * 2816
    forward = 2 * sum(parts.values())
    assert 470e6 < forward < 490e6          # ISSUE 27's arithmetic: 474 MFLOP
    step = costs_mla_moe.step_flops(cfg, ACTION_DIM)
    assert step == pytest.approx(4 * 8000 * forward, rel=0.07)  # + the torso


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    """Every key of the source's config.json stands at the top of the file
    with the source's value, except the depth, which is as run and listed in
    ``reduced``; the program's overrides say the same; the widths are the
    row's own."""
    source = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 11264, "kv_lora_rank": 512,
        "max_position_embeddings": 8192, "model_type": "deepseek_v3",
        "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 6, "num_hidden_layers": 27,
        "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    differs = {k for k, v in source.items() if CONFIG.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"}
    assert CONFIG["num_hidden_layers"] == 5
    bm_structure.moonlight_config_entry(BENCH)
    overrides = CONFIG["overrides"]
    for key, value in overrides.items():
        name = key.split(".")[-1]
        if key.startswith("network.core.") and name in source \
                and name != "num_hidden_layers":
            assert value == source[name], key
    assert overrides["network.core.num_hidden_layers"] == 5
    assert overrides["network.core.experts_held"] == CONFIG["experts_held"] == 8
    assert "eight chips share each layer" in CONFIG["deployment"]


def test_cell_joins_the_accepted_metrics():
    bm_structure.moonlight_cell_joins(BENCH)
    table = harness.scope_table(CONFIG)
    tokens = [token for token, _ in table]
    assert tokens.index("moe_experts") < tokens.index("mem_core") \
        < tokens.index("torso")
    r2d2 = harness.scope_table(harness.config_doc(BENCH, "r2d2-paper"))
    assert table[-len(r2d2):] == r2d2
    # no token is a part of another scope's name
    for a in tokens:
        assert not any(a != b and a in b for b in tokens), a


def _fixture_summary(config):
    from benchmarks.trace import reduce, xspace_text
    (path,) = glob.glob(os.path.join(harness.BENCH_DIR, "trace", "fixtures",
                                     "*.txt.gz"))
    return reduce.summarize_data(xspace_text.load(path),
                                 scopes=harness.scope_table(config))


def _cfg(config_name, mix):
    return harness.build_config(harness.program_overrides(
        harness.config_doc(BENCH, config_name), harness.traffic_doc(mix)),
        "unused", 0)


@pytest.mark.parametrize("reader", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_core(reader):
    """The parent's program has no ``mem_core`` scope and counts no routed
    pairs: on its capture (the recorded r2d2-ref.learner fixture) a new
    reader returns nothing and does not raise, with or without a trace, and
    whether or not a count of pairs comes with it."""
    summary = _fixture_summary(CONFIG)
    assert summary.busy_s() > 0
    cfg = _cfg("r2d2-ref", "learner")
    counted = {"steps": 16, "pairs_held": 96000, "rows_walked": 122880}
    for trace in (summary, None):
        for extra in ({}, {"moe_traced": counted}):
            ctx = run.MetricContext(
                cfg=cfg, values={}, trace=trace, device_kind="TPU v5 lite",
                config=CONFIG, facts={"steps_per_dispatch": 16,
                                      "action_dim": ACTION_DIM, **extra})
            assert harness.reader_of(reader).read(ctx) is None


COUNTS = {"r2d2-ref": costs, "r2d2-paper": costs,
          "moonlight-core": costs_mla_moe}


@pytest.mark.parametrize("config_name, mix", [
    ("r2d2-ref", "learner"), ("r2d2-paper", "learner"),
    ("moonlight-core", "learner-long")])
def test_mfu_bf16_reads_the_count_its_configuration_names(config_name, mix):
    """One reader for the step's share of the chip: the configuration's file
    names the module that counts its model work (``costs``), and the reading
    is that count over the step's device time and the peak. Here on the
    recorded capture (one program of 16 steps), whose step time all three
    are held to."""
    config = harness.config_doc(BENCH, config_name)
    assert harness.costs_of(config) is COUNTS[config_name]
    summary, cfg = _fixture_summary(config), _cfg(config_name, mix)
    ctx = run.MetricContext(
        cfg=cfg, values={}, trace=summary, device_kind="TPU v5 lite",
        config=config, facts={"steps_per_dispatch": 16,
                              "action_dim": ACTION_DIM})
    (program,) = summary.module_runs("loss")
    step_s = program.dur / 1e9 / 16
    flops = COUNTS[config_name].step_flops(cfg, ACTION_DIM)
    got = harness.reader_of("mfu_bf16").read(ctx)
    assert got == pytest.approx(100 * flops / step_s / 197e12, rel=1e-12)
    if config_name == "r2d2-ref":
        # the fixture is this cell's step as PR 22 recorded it (13.1 ms)
        assert 20 < got < 25
    if config_name == "moonlight-core":
        # 16.26 TFLOP of model work a step (PERF.md, section 5)
        assert flops == pytest.approx(16.26e12, rel=2e-3)


@pytest.mark.parametrize("config", [None, {}, {"costs": None}],
                         ids=["no_file", "no_key", "null"])
def test_mfu_bf16_finds_nothing_where_no_count_is_named(config):
    summary = _fixture_summary(CONFIG)
    ctx = run.MetricContext(
        cfg=_cfg("r2d2-ref", "learner"), values={}, trace=summary,
        device_kind="TPU v5 lite", config=config,
        facts={"steps_per_dispatch": 16, "action_dim": ACTION_DIM})
    assert harness.reader_of("mfu_bf16").read(ctx) is None
    assert harness.reader_of("k_experts_roofline").read(ctx) is None


def test_a_count_that_is_named_and_has_no_file_is_an_error():
    with pytest.raises(harness.BenchError, match="no_such_count"):
        harness.costs_of({"costs": "no_such_count"})


STEP = "jit(multi_step)/jit(main)/while/body/"
CORE = STEP + "R2D2Network/mem_core/layers_1/mlp/"


def _experts_capture(products_ns=3000, steps=2):
    """``steps`` train steps in one program: in each the held experts'
    activation under ``moe_experts`` (1,000 ns), their grouped products as
    the chip's capture names them (an ``op_name`` that is the call's own
    name and no scope, ``products_ns``), the sort of the pairs, a fusion of
    the attention, the loss."""
    from jax.profiler import ProfileData

    from benchmarks.trace import reduce, xspace_text

    def op(name, start, dur, path):
        return (f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %p)", start, dur,
                {"op_name": path})
    ops, width = [], products_ns + 4000
    for i in range(steps):
        at = 1000 + i * width
        ops += [
            op(f"fusion.{i}1", at, 1000, CORE + "moe_experts/mul"),
            op(f"ragged-dot-none.{i}2", at + 1000, products_ns,
               "ragged-dot-none"),
            op(f"sort.{i}3", at + 1000 + products_ns, 500,
               CORE + "moe_dispatch/sort"),
            op(f"fusion.{i}4", at + 1500 + products_ns, 2000,
               STEP + "R2D2Network/mem_core/layers_1/mla_attn/dot_general"),
            op(f"fusion.{i}5", at + 3500 + products_ns, 500,
               STEP + "loss/sub"),
        ]
    capture = {"/device:TPU:0": {
        "XLA Modules": [("jit_multi_step(1)", 1000, steps * width, {})],
        "XLA Ops": ops}}
    return reduce.summarize_data(
        ProfileData.from_text_proto(xspace_text.to_text(capture)),
        harness.scope_table(CONFIG))


def _experts_ctx(summary, counted, steps_per_dispatch=2):
    facts = {"steps_per_dispatch": steps_per_dispatch,
             "action_dim": ACTION_DIM}
    if counted is not None:
        facts["moe_traced"] = counted
    return run.MetricContext(
        cfg=_cfg("moonlight-core", "learner-long"), values={}, facts=facts,
        trace=summary, device_kind="TPU v5 lite", config=CONFIG)


# one pair: 3 products of 2048 x 1408 at 2 FLOPs a multiply-add, in four
# passes (online forward, target forward, a backward of two)
PAIR_FLOPS = 6 * 2048 * 1408 * 4


def test_experts_roofline_counts_pairs_and_never_the_rows_walked():
    read = harness.reader_of("k_experts_roofline").read
    summary = _experts_capture()
    # the rows under moe_experts and the scope-less grouped products
    assert summary.self_by_scope()["moe_experts"] == pytest.approx(8000e-9)
    counted = {"steps": 2, "pairs_held": 8, "rows_walked": 10}
    base = read(_experts_ctx(summary, counted))
    assert base == pytest.approx(
        100 * 8 * PAIR_FLOPS / 8000e-9 / 197e12)
    assert 0 < base < 100
    # twice the padding at the same pairs: the numerator does not move
    assert read(_experts_ctx(summary, {**counted, "rows_walked": 20})) == base
    # ... and what the padding costs shows as time: the reading falls with it
    slower = _experts_capture(products_ns=7000)
    assert read(_experts_ctx(slower, counted)) == pytest.approx(base / 2)
    # the count is taken a step: a block of counters that spans other
    # dispatches than the traced ones reads the same
    assert read(_experts_ctx(summary, {"steps": 6, "pairs_held": 24,
                                       "rows_walked": 30})) \
        == pytest.approx(base)
    assert costs_mla_moe.experts_flops(
        _cfg("moonlight-core", "learner-long"), 1) == PAIR_FLOPS


@pytest.mark.parametrize("counted", [
    None, {}, {"steps": 2, "pairs_held": 0, "rows_walked": 5120},
    {"steps": 0, "pairs_held": 0, "rows_walked": 0},
    {"steps": 2, "rows_walked": 5120}],
    ids=["absent", "empty", "no_pair_held", "no_step", "no_such_counter"])
def test_experts_roofline_finds_nothing_without_a_count_of_pairs(counted):
    """A program that counts no pairs, a step whose router put none on a
    held expert, a checkout from before the counter: None, and no 0 for a
    share of a peak."""
    read = harness.reader_of("k_experts_roofline").read
    assert read(_experts_ctx(_experts_capture(), counted)) is None
    assert read(_experts_ctx(None, counted)) is None


def test_the_other_readers_of_the_core_read_the_made_up_rows():
    summary = _experts_capture()
    ctx = _experts_ctx(summary, None)
    busy = summary.busy_s()
    assert busy == pytest.approx(14000e-9)
    # the core's share holds the grouped products, which carry no scope
    for reader, ns in (("core_self_share", 13000), ("mla_self_share", 4000),
                       ("moe_self_share", 9000),
                       ("moe_dispatch_self_share", 1000)):
        assert harness.reader_of(reader).read(ctx) == pytest.approx(
            100 * ns * 1e-9 / busy), reader


def test_runner_hands_on_the_counters_of_the_last_flush():
    from benchmarks.runners import learner
    layer = {"chosen_hist": [], "pairs_held": 5, "held_load_max": 3,
             "held_load_mean": 1.0, "router_entropy": 1.0, "dropped": 0}
    block = {"steps": 8, "layers": [dict(layer, rows_walked=8),
                                    dict(layer, pairs_held=7)]}
    assert learner._moe_totals(block) == {
        "steps": 8, "pairs_held": 12, "rows_walked": 8}

    class Metrics:
        _moe = None

    class Learner:
        metrics = Metrics()

        def flush_metrics(self):
            self.metrics._moe, self.pending = self.pending, None

    loop = learner._Loop(Learner(), harness.HostSpans())
    loop.learner.pending = block
    assert loop.flush() is block
    assert loop.flush() is None             # nothing new was dispatched
    del Metrics._moe                        # a program without the counter
    assert loop.flush() is None


def test_runner_gives_the_learners_loop_a_subwindow_in_dispatches():
    mix = harness.traffic_doc("learner-long")
    assert mix["runner"] == "learner_long"
    assert "subwindow_steps" not in mix["parameters"]
    assert learner_long.load_program is learner_long.learner.load_program
    cfg = harness.build_config(harness.program_overrides(CONFIG, mix),
                               "unused", 0)
    seen = {}

    class Ctx:
        traffic = harness.traffic_parameters(mix)

    Ctx.cfg = cfg
    original = learner_long.learner.run
    learner_long.learner.run = lambda ctx: seen.update(ctx.traffic) or "ran"
    try:
        assert learner_long.run(Ctx) == "ran"
    finally:
        learner_long.learner.run = original
    assert cfg.runtime.resolved_steps_per_dispatch() == 4
    assert seen["subwindow_steps"] == 8 == 2 * 4
