"""The ``lfm2-core`` configuration: the program's ``conv_attn_moe`` core
against the plain reference (forward, loss and gradients, float32 and bf16,
through ``check.compare``, with stored state in both kinds of part of the
row), the controls of the comparison (a kind of part zeroed, the experts left
out), the four chips' shares of the experts against the uncut layer, the
training record's counters and its ``core`` block, the benchmark's count of
operations against a hand count, the files' contract with the catalog, the
cell's entries in ``BENCHMARK.json``, the new readers, and the ``mla_moe``
program left as it was. Tiny sizes, CPU, seeded random weights."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_structure
from benchmarks import costs_lfm2, harness, run, traffic
from benchmarks.reference import check, r2d2_lfm2

BENCH = harness.load_benchmark()
CELL = "lfm2-core.learner-long"
CONFIG = harness.config_doc(BENCH, "lfm2-core")
POOL = {"pool_blocks": 4, "priority_range": [0.1, 2.0], "reward_scale": 1.0}
ACTION_DIM = 6
# the cell's name at the end of these lists (ISSUE 33)
JOINED = ["seq_updates_per_s", "dispatch_host_ms", "train_step_ms",
          "mfu_bf16", "torso_self_share", "k_decode_roofline",
          "k_gather_roofline", "device_idle_share"]
# the cell's own per-layer entries, in their order: (layer, better)
READERS = {"lfm2-core.core_self_share": ("memory_core", "lower"),
           "lfm2-core.moe_self_share": ("memory_core", "lower"),
           "lfm2-core.moe_dispatch_self_share": ("memory_core", "lower"),
           "lfm2-core.k_experts_roofline": ("kernels", "higher"),
           "conv_self_share": ("memory_core", "lower"),
           "gqa_self_share": ("memory_core", "lower")}


def _tiny_cfg(tmp_path, seed=0, **extra):
    overrides = harness.program_overrides(CONFIG, {}, rehearse=True)
    # the rehearsal's twin is all dense (the configuration's
    # ``rehearsal_note`` says why); here its layers 1 and 2 route
    return harness.build_config({**overrides, "runtime.save_interval": 0,
                                 "runtime.steps_per_dispatch": 2,
                                 "network.core.first_k_dense_replace": 1,
                                 **extra}, str(tmp_path), seed)


def _tiny_learner(tmp_path, seed=0, **extra):
    """The configuration's tiny CPU twin on a full ring (every sequence's
    stored row random in both kinds of part), a target net that differs from
    the online one, and an expert bias that is not zero."""
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner

    cfg = _tiny_cfg(tmp_path, seed, **extra)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    learner = Learner(cfg, net, 0, seed=seed)
    traffic.fill_ring(learner, ACTION_DIM, POOL, seed)

    def seeded_bias(params, key):
        for name in ("layers_1", "layers_2"):
            mlp = params["params"]["mem_core"][name]["mlp"]
            key, k = jax.random.split(key)
            # of the size of the scores' spread at these widths, so that
            # both decide the choice
            mlp["e_score_correction_bias"] = 0.03 * jax.random.normal(
                k, mlp["e_score_correction_bias"].shape)
        return params

    state = learner.train_state
    learner.train_state = state.replace(
        params=seeded_bias(jax.tree_util.tree_map(lambda x: x, state.params),
                           jax.random.PRNGKey(seed + 5)),
        target_params=seeded_bias(net.init(jax.random.PRNGKey(seed + 99)),
                                  jax.random.PRNGKey(seed + 6)))
    return learner


# -- the program against the plain reference ----------------------------------


# float32 on both sides, one backend: they differ in the order of summation
# (and in the grouped product against the per-expert loop), about 1e-6
@pytest.mark.parametrize("double", [False, True])
def test_program_loss_matches_reference_in_float32(tmp_path, double):
    learner = _tiny_learner(tmp_path, **{"network.use_double": double})
    try:
        out = check.check_learner(learner, "r2d2_lfm2", 8, seed=7)
    finally:
        learner.stop_background()
    assert out["compute_dtype"] == "float32" and out["tolerance"] == 5e-5
    assert out["ok"], out
    assert out["valid_steps"] > 0 and out["stable_steps"] > 0


def test_bf16_program_passes_its_tolerance_and_fails_float32s(tmp_path):
    learner = _tiny_learner(tmp_path, **{"network.bf16": "on"})
    try:
        program, reference, weights, dtype = check.program_and_reference(
            learner, "r2d2_lfm2", 8, seed=7)
    finally:
        learner.stop_background()
    assert dtype == "bfloat16"
    assert check.compare(program, reference, weights, 5e-2)["ok"]
    assert not check.compare(program, reference, weights, 5e-5)["ok"]


def _sampled(tmp_path, n=8):
    """(cfg, net, spec of ``n`` sequences, the sequences, online and target
    parameters) of the tiny learner."""
    learner = _tiny_learner(tmp_path)
    try:
        batch = check.sample_sequences(learner, n, seed=3)
        params, target = jax.device_get(
            (learner.train_state.params, learner.train_state.target_params))
    finally:
        learner.stop_background()
    spec = dataclasses.replace(learner.spec, batch_size=n)
    return learner.cfg, learner.net, spec, batch, params, target


def _fields(batch):
    from r2d2_tpu.replay.structs import SampleBatch
    return {f.name: getattr(batch, f.name)
            for f in dataclasses.fields(SampleBatch)
            if getattr(batch, f.name) is not None}


def test_program_gradients_match_the_references(tmp_path):
    from r2d2_tpu.learner.train_step import make_loss_fn
    cfg, net, spec, batch, params, target = _sampled(tmp_path)
    program = jax.grad(lambda p: make_loss_fn(
        net, spec, cfg.optim, True)(p, target, batch)[0])(params)
    reference = jax.grad(lambda p: r2d2_lfm2.from_config(cfg)(
        p, target, _fields(batch))["loss"])(params)
    flat_p = jax.tree_util.tree_leaves_with_path(program)
    flat_r = jax.tree_util.tree_leaves(reference)
    assert len(flat_p) == len(flat_r) > 30
    kinds = set()
    for (path, got), want in zip(flat_p, flat_r):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(want).max())
        if any(leaf in name for leaf in ("e_score_correction_bias",
                                         "router_input_mean")):
            assert scale == 0.0 and float(jnp.abs(got).max()) == 0.0
            continue
        assert scale > 0, path
        np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0,
                                   err_msg=name)
        kinds |= {k for k in ("'conv'", "'self_attn'", "'experts'")
                  if k in name}
    assert len(kinds) == 3      # both kinds of operator, and the experts


def test_the_comparison_sees_each_kind_of_stored_part_and_the_experts(
        tmp_path):
    """The controls ISSUE 33 asks of the chip run, here at the tiny size:
    against the reference on the same sequences, the program with the row's
    conv parts zeroed, with its key/value part zeroed, and with the held
    experts left out each fail the float32 comparison that the sound
    program passes."""
    from r2d2_tpu.learner.train_step import make_loss_fn
    from r2d2_tpu.models.cores.conv_attn_moe import state_layout
    cfg, net, spec, batch, params, target = _sampled(tmp_path)
    reference = jax.device_get(r2d2_lfm2.from_config(cfg)(
        params, target, _fields(batch)))

    def errors(params=params, target=target, batch=batch):
        loss, aux = jax.jit(make_loss_fn(net, spec, cfg.optim, True))(
            params, target, batch)
        out = jax.device_get({"loss": loss, **{
            k: aux[k] for k in ("priorities", "q_chosen", "abs_td")}})
        return check.compare(out, reference, batch.is_weights, 5e-5)

    def without(kind):
        row = np.array(batch.hidden).reshape(len(batch.hidden), -1)
        for part in state_layout(cfg.network.core):
            if part.kind == kind:
                row[:, part.offset:part.offset + part.size] = 0.0
        return dataclasses.replace(
            batch, hidden=row.reshape(np.shape(batch.hidden)))

    def no_experts(p):
        p = jax.tree_util.tree_map(lambda x: x, p)
        for layer in p["params"]["mem_core"].values():
            if isinstance(layer, dict) and "experts" in layer.get("mlp", {}):
                experts = layer["mlp"]["experts"]
                experts["down_proj"] = np.zeros_like(experts["down_proj"])
        return p

    sound = errors()
    assert sound["ok"] and sound["errors"]["q_chosen"] < 2e-6
    for control in (dict(batch=without("full_attention")),
                    dict(params=no_experts(params),
                         target=no_experts(target))):
        out = errors(**control)
        assert not out["ok"], out["errors"]
    # a conv part is the two positions before the window: it reaches a
    # learning step only through what the attention layer keeps of the
    # window's first two positions, burn-in steps away. The comparison sees
    # it, far above the sound program's error, and (at these tiny widths)
    # inside the tolerance
    conv = errors(batch=without("conv"))["errors"]["q_chosen"]
    assert conv > 20 * sound["errors"]["q_chosen"]


def test_the_references_layout_is_the_programs():
    """The reference writes the state row's layout out again; the program's
    ``state_layout`` is the one function its stack and its tests read."""
    from r2d2_tpu.models.cores import state_half
    from r2d2_tpu.models.cores.conv_attn_moe import state_layout
    for overrides in (harness.program_overrides(CONFIG, {}),
                      harness.program_overrides(CONFIG, {}, rehearse=True)):
        core = harness.build_config(overrides, "unused", 0).network.core
        ours = [(p.kind, p.offset, p.shape) for p in state_layout(core)]
        assert ours == r2d2_lfm2.layout(dataclasses.asdict(core))
    # the cell's row: 4 x (2 x 2048) + 128 x 1024 floats = 576 KiB
    cfg = harness.build_config(harness.program_overrides(CONFIG, {}),
                               "unused", 0)
    assert state_half(cfg.network) == 73_728
    assert 8 * state_half(cfg.network) == 576 * 1024


# -- the chip's share of the experts -------------------------------------------


def _moe_layer(core, key, positions=48):
    """An expert layer's feed-forward half as the learner runs it (its
    norm's weight all ones), seeded parameters with an expert bias that is
    not zero, and the normed positions the reference is given."""
    from r2d2_tpu.models.cores.conv_attn_moe import MoE
    from r2d2_tpu.models.cores.experts import rms_norm
    ones = jnp.ones((core.hidden_size,))
    x = jax.random.normal(key, (2, positions // 2, core.hidden_size))
    params = MoE(core, jnp.float32, True).init(jax.random.PRNGKey(1), x,
                                               ones)["params"]
    assert "shared_experts" not in params
    params["e_score_correction_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(2), (core.n_routed_experts,))

    def layer(core, params):
        out, stats = MoE(core, jnp.float32, True).apply({"params": params},
                                                        x, ones)
        return out.reshape(-1, core.hidden_size), stats

    flat = rms_norm(x, ones, core.rms_norm_eps).reshape(-1, core.hidden_size)
    return layer, params, flat


def test_the_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """Four chips, 8 of the 32 routed experts each, top 4 as published (the
    widths tiny): their parts are the layer that holds all 32, which is the
    reference's uncut layer. No shared expert is there to count once."""
    from r2d2_tpu.config import CoreConfig
    sizes = {k.split(".")[-1]: v for k, v in CONFIG["rehearsal"].items()
             if k.startswith("network.core.")}
    whole = CoreConfig(**{"kind": "conv_attn_moe", **sizes,
                          "n_routed_experts": 32, "num_experts_per_tok": 4,
                          "experts_held": 32, "expert_offset": 0,
                          "routed_scaling_factor": 1.0})
    layer, params, flat = _moe_layer(whole, jax.random.PRNGKey(0))
    uncut, stats = layer(whole, params)
    reference = r2d2_lfm2._experts(flat, params, dataclasses.asdict(whole))
    np.testing.assert_allclose(uncut, reference, atol=2e-6)
    total = jnp.zeros_like(uncut)
    for chip in range(4):
        share = dataclasses.replace(whole, experts_held=8,
                                    expert_offset=8 * chip)
        mine = {**params, "experts": {
            k: v[8 * chip:8 * chip + 8] for k, v in params["experts"].items()}}
        out, counted = layer(share, mine)
        np.testing.assert_allclose(out, r2d2_lfm2._experts(
            flat, mine, dataclasses.asdict(share)), atol=2e-6)
        assert int(counted["dropped"]) == 0
        total = total + out
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert stats["chosen"].shape == (32,)
    assert int(stats["chosen"].sum()) == flat.shape[0] * 4


# -- the record ----------------------------------------------------------------


def test_training_record_carries_the_routing_counters_and_the_rows_parts(
        tmp_path):
    learner = _tiny_learner(tmp_path)
    try:
        before = jax.device_get(learner.train_state.params)
        for _ in range(3):
            learner.step()
        learner.flush_metrics()
        block, core_block = learner.metrics._moe, learner.metrics._core
        after = jax.device_get(learner.train_state)
    finally:
        learner.stop_background()
    cfg = learner.cfg
    core = cfg.network.core
    pairs = (cfg.replay.batch_size * cfg.sequence.seq_len
             * core.num_experts_per_tok)
    assert block["steps"] == 6 and len(block["layers"]) == 2
    for layer in block["layers"]:
        assert len(layer["chosen_hist"]) == core.n_routed_experts
        assert sum(layer["chosen_hist"]) == 6 * pairs
        assert layer["pairs_held"] == sum(
            layer["chosen_hist"][:core.experts_held])
        assert 0 < layer["pairs_held"] <= layer["rows_walked"]
        assert 0 < layer["router_entropy"] <= np.log(
            core.n_routed_experts) + 1e-6
        assert layer["dropped"] == 0
    # what a sequence's stored row holds, by kind of part
    assert core_block["kind"] == "conv_attn_moe"
    assert core_block["parts"] == [
        {"kind": "conv_state", "layers": 2, "floats": 2 * 2 * 32,
         "bytes": 4 * 2 * 2 * 32},
        {"kind": "key_value_window", "layers": 1, "floats": 4 * 2 * 2 * 8,
         "bytes": 4 * 4 * 2 * 2 * 8}]
    assert core_block["row_bytes"] == sum(
        part["bytes"] for part in core_block["parts"])
    # the train step stores the mean each router centred its input on
    for name in ("layers_1", "layers_2"):
        def stored_mean(params):
            return params["params"]["mem_core"][name]["mlp"][
                "router_input_mean"]
        assert np.abs(stored_mean(before)).max() == 0
        assert np.abs(stored_mean(after.params)).max() > 0


# -- the count of operations ---------------------------------------------------


def test_count_of_operations_is_the_hand_count_at_a_small_size():
    """One conv layer with the dense SwiGLU and one attention layer with
    experts, d = 8, 2 query heads on 1 key/value head of 4, a window of 4
    steps over 2 stored positions, batch 3: every product written out."""
    from r2d2_tpu.config import Config
    cfg = Config().replace(**{
        "env.frame_stack": 1, "env.frame_height": 6, "env.frame_width": 6,
        "network.conv_layers": ((2, 3, 1),), "network.cnn_out_dim": 5,
        "network.hidden_dim": 7, "network.use_dueling": True,
        "network.use_double": True, "sequence.burn_in_steps": 1,
        "sequence.learning_steps": 2, "sequence.forward_steps": 1,
        "replay.batch_size": 3, "replay.block_length": 4,
        "replay.capacity": 16,
        "network.core.kind": "conv_attn_moe", "network.core.hidden_size": 8,
        "network.core.num_attention_heads": 2,
        "network.core.num_key_value_heads": 1,
        "network.core.layer_types": ("conv", "full_attention"),
        "network.core.num_hidden_layers": 2,
        "network.core.intermediate_size": 16,
        "network.core.moe_intermediate_size": 6,
        "network.core.n_routed_experts": 4,
        "network.core.num_experts_per_tok": 2,
        "network.core.experts_held": 1, "network.core.memory_len": 2})
    parts = costs_lfm2.core_macs_per_position(cfg.network.core, 5 + 3, 4)
    assert parts == {
        "input_proj": 8 * 8,
        "short_conv": 8 * 24 + 8 * 8,
        # q 8x8, k and v 8x4 each, out 8x8; (2 + 2.5) keys x 2 heads x (4 + 4)
        "gqa_attn": 64 + 32 + 32 + 64 + 4.5 * 2 * 8,
        "dense_mlp": 3 * 8 * 16,
        "moe_router": 8 * 4,
        # 2 choices x 1 of 4 experts held = half a pair a position
        "moe_experts": 0.5 * 3 * 8 * 6,
    }
    # torso: a 3x3 conv of 1 -> 2 channels on 4x4 outputs, a dense 32 -> 5;
    # head: two streams 8 -> 7, then 7 -> 3 + 1
    first_conv = 16 * 9 * 2
    outer = first_conv + 32 * 5 + 2 * 8 * 7 + 7 * 4
    positions = 3 * 4
    assert costs_lfm2.step_flops(cfg, 3) == pytest.approx(
        2 * positions * ((outer + sum(parts.values())) * 4 - first_conv))
    # a held pair: three products of 8 x 6, four passes
    assert costs_lfm2.experts_flops(cfg, 1) == 2 * 3 * 8 * 6 * 4


def test_the_cells_count_is_the_issues_arithmetic():
    cfg = harness.build_config(harness.program_overrides(
        CONFIG, harness.traffic_doc("learner-long")), "unused", 0)
    parts = costs_lfm2.core_macs_per_position(cfg.network.core, 1030, 125)
    assert parts["short_conv"] == 4 * 4 * 2048 * 2048
    assert parts["dense_mlp"] == 3 * 2048 * 7168
    # 4 expert layers x 1 pair a position x 3 x 2048 x 1792
    assert parts["moe_experts"] == pytest.approx(4 * 3 * 2048 * 1792)
    forward = 2 * sum(parts.values())
    assert 330e6 < forward < 345e6       # 2.7 TFLOP a pass of 8,000 positions
    step = costs_lfm2.step_flops(cfg, ACTION_DIM)
    assert step == pytest.approx(4 * 8000 * forward, rel=0.1)   # + the torso
    assert costs_lfm2.experts_flops(cfg, 1) == 6 * 2048 * 1792 * 4
    assert harness.costs_of(CONFIG) is costs_lfm2


# -- the files -----------------------------------------------------------------


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    """Every key of the source's config.json stands at the top of the file
    with the source's value, except the depth, the dense layers and the
    layers' kinds, which are as run and listed in ``reduced``; the program's
    overrides say the same; the widths are the row's own."""
    period = ["full_attention", "conv", "conv", "conv"]
    source = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": ["conv", "conv"] + 4 * period + period[:3] + period[:3],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert len(source["layer_types"]) == 24
    differs = {k for k, v in source.items() if CONFIG.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    assert differs <= set(CONFIG["reduced"])
    assert CONFIG["published"] == {k: source[k] for k in differs}
    # the layers as run are the published layers 1-5: one leading dense layer
    # and one whole period
    assert CONFIG["layer_types"] == source["layer_types"][1:6] \
        == ["conv"] + period
    assert CONFIG["num_hidden_layers"] == 5 and CONFIG["num_dense_layers"] == 1
    assert CONFIG["reduced"] == [
        "network.core.num_hidden_layers", "network.core.experts_held",
        "replay.capacity", "num_hidden_layers", "num_dense_layers",
        "layer_types"]
    overrides = CONFIG["overrides"]
    spelt = {"n_routed_experts": "num_experts", "rms_norm_eps": "norm_eps",
             "first_k_dense_replace": "num_dense_layers"}
    for key, value in overrides.items():
        name = key.split(".")[-1]
        name = spelt.get(name, name)
        if key.startswith("network.core.") and name in source:
            assert value == CONFIG[name], key
    assert overrides["network.core.experts_held"] == CONFIG["experts_held"] == 8
    assert overrides["network.core.memory_len"] == 128
    assert "four chips share each layer" in CONFIG["deployment"]
    # the window and batch of r2d2-paper: the two cells differ in the core
    paper = harness.config_doc(BENCH, "r2d2-paper")["overrides"]
    same = {k: v for k, v in overrides.items()
            if k in paper and k != "replay.capacity"}
    assert same == {k: v for k, v in paper.items() if k != "replay.capacity"}
    assert overrides["replay.capacity"] == 50_000
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-core")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]


@pytest.mark.parametrize("holds", bm_structure.ALL, ids=lambda f: f.__name__)
def test_structure_holds_as_the_file_stands_and_with_more_appended(
        holds, tmp_path):
    holds(BENCH)
    holds(*bm_structure.appended_copy(BENCH, str(tmp_path)))


def test_the_cell_joins_the_lists_and_brings_its_own_readers():
    bm_structure.cell_resolves(BENCH, CELL)
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-core", "learner-long", 1)
    for name in JOINED:
        assert bm_structure.in_order(
            bm_structure.LSTM_LEARNERS + [bm_structure.MOONLIGHT, CELL],
            bm_structure.metric(BENCH, name)["workloads"]), name
    assert CELL not in bm_structure.metric(BENCH, "lstm_self_share")[
        "workloads"]
    # the five lists that are moonlight-core's alone stay so
    for name in bm_structure.MOONLIGHT_READERS:
        assert bm_structure.metric(BENCH, name)["workloads"] == [
            bm_structure.MOONLIGHT]
    names = bm_structure.per_layer_names(BENCH)
    assert bm_structure.contiguous(["k_experts_roofline"] + list(READERS),
                                   names)
    for name, (layer, better) in READERS.items():
        assert bm_structure.metric(BENCH, name) == {
            "name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "seq_updates_per_s", "workloads": [CELL]}
    in_cell = {m["name"] for m in harness.cell_metrics(BENCH, CELL,
                                                       "per_layer")}
    assert in_cell == set(READERS) | set(JOINED) - {"seq_updates_per_s"}
    assert [m["name"] for m in harness.cell_metrics(
        BENCH, CELL, "end_to_end")] == ["seq_updates_per_s", "hbm_peak_gib",
                                        "setup_s"]
    # the readings declared again are read by the files that are there
    for name in list(READERS)[:4]:
        assert harness.reader_of(name) is harness.reader_of(
            name.split(".", 1)[1])


def test_scope_table_puts_the_inner_scopes_first():
    table = harness.scope_table(CONFIG)
    tokens = [token for token, _ in table]
    assert tokens[:9] == ["ragged-dot", "short_conv", "gqa_attn", "dense_mlp",
                          "moe_router", "moe_dispatch", "moe_experts",
                          "moe_combine", "mem_core"]
    assert dict(table)["ragged-dot"] == "moe_experts"
    r2d2 = harness.scope_table(harness.config_doc(BENCH, "r2d2-paper"))
    assert table[9:] == r2d2
    # no token is a part of another scope's name
    for a in tokens:
        assert not any(a != b and a in b for b in tokens), a


# -- the readers ---------------------------------------------------------------


def _cfg(config_name, mix):
    return harness.build_config(harness.program_overrides(
        harness.config_doc(BENCH, config_name), harness.traffic_doc(mix)),
        "unused", 0)


@pytest.mark.parametrize("reader", list(READERS))
def test_new_readers_find_nothing_in_a_program_without_the_core(reader):
    """The parent's program has none of the scopes and counts no routed
    pairs: on its capture (the recorded r2d2-ref.learner fixture) a new
    reader returns nothing and does not raise, with or without a trace."""
    import glob
    import os

    from benchmarks.trace import reduce, xspace_text
    (path,) = glob.glob(os.path.join(harness.BENCH_DIR, "trace", "fixtures",
                                     "*.txt.gz"))
    summary = reduce.summarize_data(xspace_text.load(path),
                                    scopes=harness.scope_table(CONFIG))
    assert summary.busy_s() > 0
    for trace in (summary, None):
        ctx = run.MetricContext(
            cfg=_cfg("r2d2-ref", "learner"), values={}, trace=trace,
            device_kind="TPU v5 lite", config=CONFIG,
            facts={"steps_per_dispatch": 16, "action_dim": ACTION_DIM})
        assert harness.reader_of(reader).read(ctx) is None


STEP = "jit(multi_step)/jit(main)/while/body/"
CORE = STEP + "R2D2Network/mem_core/"


def _capture(steps=2):
    """``steps`` train steps in one program, each: a conv layer's input
    projection (2,000 ns), the attention's scores (1,000), the experts'
    activation (500) and their grouped products as the chip names them
    (2,500), the sort of the pairs (500), the router (250), the final norm
    under the core's own scope (250), the loss (1,000)."""
    from jax.profiler import ProfileData

    from benchmarks.trace import reduce, xspace_text

    def op(name, start, dur, path):
        return (f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %p)", start, dur,
                {"op_name": path})
    rows = [("fusion", 2000, CORE + "layers_0/short_conv/conv/dot_general"),
            ("fusion", 1000, CORE + "layers_1/gqa_attn/self_attn/dot_general"),
            ("fusion", 500, CORE + "layers_1/mlp/moe_experts/mul"),
            ("ragged-dot-none", 2500, "ragged-dot-none"),
            ("sort", 500, CORE + "layers_1/mlp/experts/moe_dispatch/sort"),
            ("fusion", 250, CORE + "layers_1/mlp/moe_router/logistic"),
            ("fusion", 250, CORE + "norm/mul"),
            ("fusion", 1000, STEP + "loss/sub")]
    ops, at = [], 1000
    for i in range(steps):
        for j, (name, dur, path) in enumerate(rows):
            ops.append(op(f"{name}.{i}{j}", at, dur, path))
            at += dur
    capture = {"/device:TPU:0": {
        "XLA Modules": [("jit_multi_step(1)", 1000, at - 1000, {})],
        "XLA Ops": ops}}
    return reduce.summarize_data(
        ProfileData.from_text_proto(xspace_text.to_text(capture)),
        harness.scope_table(CONFIG))


def test_the_cells_readers_read_the_made_up_rows():
    summary = _capture()
    busy = summary.busy_s()
    assert busy == pytest.approx(16000e-9)
    counted = {"steps": 2, "pairs_held": 8, "rows_walked": 12}
    ctx = run.MetricContext(
        cfg=_cfg("lfm2-core", "learner-long"), values={}, trace=summary,
        device_kind="TPU v5 lite", config=CONFIG,
        facts={"steps_per_dispatch": 2, "action_dim": ACTION_DIM,
               "moe_traced": counted})
    for reader, ns in (("conv_self_share", 4000), ("gqa_self_share", 2000),
                       ("lfm2-core.core_self_share", 14000),
                       ("lfm2-core.moe_self_share", 7500),
                       ("lfm2-core.moe_dispatch_self_share", 1000)):
        assert harness.reader_of(reader).read(ctx) == pytest.approx(
            100 * ns * 1e-9 / busy), reader
    # 4 pairs a step: 6 x 2048 x 1792 FLOPs a pair a pass, four passes, over
    # the 3,000 ns a step under moe_experts and the grouped products
    got = harness.reader_of("lfm2-core.k_experts_roofline").read(ctx)
    assert got == pytest.approx(
        100 * 2 * 4 * 6 * 2048 * 1792 * 4 / 6000e-9 / 197e12)
    assert 0 < got < 100


# -- the other core's program --------------------------------------------------

# sha256 of the StableHLO text of moonlight-core's tiny twin's fused learner
# step (2 steps a dispatch, learning and replay diagnostics in), lowered on
# the CPU, as the parent commit 710f057 lowers it (CPU, count, PR 33). Moving
# the held experts into models/cores/experts.py changed no operation of the
# mla_moe program. A later PR that means to change that program (or the step
# around it) records the new value here, as BASELINE.json's costs are
# re-recorded; the cell's step lowered for a TPU at full size is compared
# with the kernels' bodies printed without their source locations (PERF.md,
# Findings, PR 33).
MOONLIGHT_TINY_STEP = \
    "3bf0abe269834e79cece76c955d82efc3025fb8f51367f9a667a0f799a259da7"


def test_the_mla_moe_program_is_the_parents():
    from r2d2_tpu.config import Config
    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_multi_learner_step)
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.replay.device_replay import replay_init
    from r2d2_tpu.replay.structs import ReplaySpec
    from r2d2_tpu.telemetry.learning import LearningDiag
    from r2d2_tpu.telemetry.replaydiag import ReplayDiag

    cfg = Config().replace(**harness.program_overrides(
        harness.config_doc(BENCH, "moonlight-core"),
        harness.traffic_doc("learner-long"), rehearse=True))
    spec = ReplaySpec.from_config(cfg)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    train = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), net, cfg.optim))
    ring = jax.eval_shape(lambda: replay_init(spec))
    step = make_multi_learner_step(
        net, spec, cfg.optim, cfg.network.use_double,
        cfg.runtime.resolved_steps_per_dispatch(),
        diag=LearningDiag.from_config(cfg), rdiag=ReplayDiag.from_config(cfg))
    text = step.lower(train, ring).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == MOONLIGHT_TINY_STEP


def test_rehearsal_names_only_keys_of_the_program():
    from r2d2_tpu.config import CoreConfig
    names = {f.name for f in dataclasses.fields(CoreConfig)}
    for part in ("overrides", "rehearsal"):
        keys = {k.split(".")[-1] for k in CONFIG[part]
                if k.startswith("network.core.")}
        assert keys <= names, keys - names
    json.dumps(CONFIG)
