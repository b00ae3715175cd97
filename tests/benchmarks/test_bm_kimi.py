"""The ``kimi-linear-core`` configuration: the program's ``kda_mla_moe`` core
against the plain reference (forward, TD loss, the state row the window
leaves and the gradients, float32 and bf16, through the cell's widened
comparison), and its controls failing it; the benchmark's count of
operations against a hand count; the files' contract with the catalog; the
cell's entries in ``BENCHMARK.json``; and the new readers, on a made-up
capture and on a program without the core. Tiny sizes, CPU, seeded random
weights."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_structure
from benchmarks import costs_kimi, harness, run, traffic
from benchmarks.reference import check, r2d2_kimi
from benchmarks.runners import learner_8k_state

BENCH = harness.load_benchmark()
CELL = "kimi-linear-core.learner-8k-state"
CONFIG = harness.config_doc(BENCH, "kimi-linear-core")
POOL = {"pool_blocks": 4, "priority_range": [0.1, 2.0], "reward_scale": 1.0}
ACTION_DIM = 6
# the cell's name at the end of these lists
JOINED = ["seq_updates_per_s", "dispatch_host_ms", "train_step_ms",
          "mfu_bf16", "torso_self_share", "k_decode_roofline",
          "k_gather_roofline", "device_idle_share", "setup_trace_lower_s",
          "setup_backend_s", "setup_first_run_s", "setup_build_s"]
# the cell's own per-layer entries, in their order: (layer, better)
READERS = {"kda_self_share": ("memory_core", "lower"),
           "k_kda_scan_roofline": ("kernels", "higher"),
           "kimi-linear-core.mla_self_share": ("memory_core", "lower"),
           "kimi-linear-core.core_self_share": ("memory_core", "lower")}
# the twin's sizes with its layers 2 and 3 routed: both mixers, both FFNs
ROUTED = {"network.core.first_k_dense_replace": 1}


def _tiny_cfg(tmp_path, seed=0, **extra):
    overrides = harness.program_overrides(CONFIG, {}, rehearse=True)
    return harness.build_config({**overrides, "runtime.save_interval": 0,
                                 "runtime.steps_per_dispatch": 2, **ROUTED,
                                 **extra}, str(tmp_path), seed)


def _tiny_learner(tmp_path, seed=0, **extra):
    """The configuration's tiny CPU twin, its layers 2 and 3 routed, on a
    full ring (every sequence's stored row random), a target net that
    differs from the online one."""
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner

    cfg = _tiny_cfg(tmp_path, seed, **extra)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    learner = Learner(cfg, net, 0, seed=seed)
    traffic.fill_ring(learner, ACTION_DIM, POOL, seed)
    learner.train_state = learner.train_state.replace(
        target_params=net.init(jax.random.PRNGKey(seed + 99)))
    return learner


def _sampled(tmp_path, n=4, **extra):
    learner = _tiny_learner(tmp_path, **extra)
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


# -- the program against the plain reference ----------------------------------


# float32 on both sides, one backend: they differ in the order of summation
# and in the grouped product against the per-expert loop, about 1e-6
def test_program_loss_and_state_match_the_reference_in_float32(tmp_path):
    learner = _tiny_learner(tmp_path)
    try:
        out = learner_8k_state.check_learner(learner, "r2d2_kimi", 8, seed=7)
    finally:
        learner.stop_background()
    assert out["compute_dtype"] == "float32" and out["tolerance"] == 5e-5
    assert out["ok"], out
    assert out["valid_steps"] > 0 and out["stable_steps"] > 0
    state = out["state"]
    assert set(state["errors"]) == {"kda_state", "conv_tail",
                                    "latent_cache"}
    assert state["limits"] == dict.fromkeys(state["errors"], 1e-4)
    assert max(state["errors"].values()) <= 1e-5
    assert [len(v) for v in state["per_layer"].values()] == [2, 2, 1]


def test_bf16_program_passes_its_tolerance_and_fails_float32s(tmp_path):
    learner = _tiny_learner(tmp_path, **{"network.bf16": "on"})
    try:
        out = learner_8k_state.check_learner(learner, "r2d2_kimi", 8, seed=7)
        program, reference, weights, _ = check.program_and_reference(
            learner, "r2d2_kimi", 8, seed=7)
    finally:
        learner.stop_background()
    assert out["compute_dtype"] == "bfloat16"
    assert out["ok"], out
    assert not check.compare(program, reference, weights, 5e-5)["ok"]
    # bf16's rounding shows in the state, inside its limit
    assert 1e-4 < max(out["state"]["errors"].values()) \
        <= learner_8k_state.STATE["bfloat16"]


def test_the_gradients_match_the_references(tmp_path):
    """The program's gradient of its TD loss is the reference's, in every
    kind of parameter: both mixers, the dense and the routed FFNs."""
    from r2d2_tpu.learner.train_step import make_loss_fn
    cfg, net, spec, batch, params, target = _sampled(tmp_path)
    loss_fn = make_loss_fn(net, spec, cfg.optim, True)
    value, aux = loss_fn(params, target, batch)
    fields = _fields(batch)
    reference = r2d2_kimi.from_config(cfg)
    np.testing.assert_allclose(value, reference(params, target,
                                                fields)["loss"], rtol=2e-5)
    # the core's counters, per KDA layer over the learning positions
    assert aux["core"]["log_decay"].shape == (2,)
    assert "loss" not in aux["core"]
    program = jax.grad(lambda p: loss_fn(p, target, batch)[0])(params)
    wanted = jax.grad(lambda p: reference(p, target, fields)["loss"])(params)
    flat_p = jax.tree_util.tree_leaves_with_path(program)
    flat_r = jax.tree_util.tree_leaves(wanted)
    assert len(flat_p) == len(flat_r) > 40
    kinds = set()
    for (path, got), want in zip(flat_p, flat_r):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(want).max())
        if "router_input_mean" in name or "e_score_correction_bias" in name:
            assert float(jnp.abs(got).max()) == 0.0
            continue
        assert scale > 0, path
        np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0,
                                   err_msg=name)
        kinds |= {k for k in ("'A_log'", "'q_conv1d'", "'kv_b_proj'",
                              "'experts'", "'shared_experts'", "'up_proj'")
                  if k in name}
    assert kinds == {"'A_log'", "'q_conv1d'", "'kv_b_proj'", "'experts'",
                     "'shared_experts'", "'up_proj'"}


def _carry_dropped(scan):
    """``scan`` with each chunk started from S = 0."""
    def dropped(q, k, v, g, beta, s0, chunk):
        b, h, t, e = q.shape
        n = -(-t // chunk)

        def split(x):
            x = jnp.pad(x, [(0, 0), (0, 0), (0, n * chunk - t)]
                        + [(0, 0)] * (x.ndim - 3))
            x = x.reshape((b, h, n, chunk) + x.shape[3:])
            return jnp.moveaxis(x, 2, 1).reshape((b * n, h, chunk)
                                                 + x.shape[4:])

        o, s = scan(split(q), split(k), split(v), split(g), split(beta),
                    jnp.zeros((b * n,) + s0.shape[1:], s0.dtype), chunk)
        o = jnp.moveaxis(o.reshape(b, n, h, chunk, -1), 1, 2)
        return (o.reshape(b, h, n * chunk, -1)[:, :, :t],
                s.reshape((b, n) + s.shape[1:])[:, -1])
    return dropped


def test_the_cells_comparison_fails_its_controls(tmp_path, monkeypatch):
    """The cell's runner widens ``check.py``'s comparison by the state row:
    the float32 twin passes it; the scan with its chunks' carry dropped,
    or with its decays ignored, fails it, on the state where Q alone lets
    a control through."""
    from r2d2_tpu.ops import kda
    learner = _tiny_learner(tmp_path)
    try:
        sound = learner_8k_state.check_learner(learner, "r2d2_kimi", 4, 3)
        plain = kda.kda_scan
        monkeypatch.setattr(kda, "kda_scan", _carry_dropped(plain))
        carry = learner_8k_state.check_learner(learner, "r2d2_kimi", 4, 3)
        monkeypatch.setattr(kda, "kda_scan", lambda q, k, v, g, *rest:
                            plain(q, k, v, jnp.zeros_like(g), *rest))
        decay = learner_8k_state.check_learner(learner, "r2d2_kimi", 4, 3)
    finally:
        learner.stop_background()
    assert sound["ok"]
    for control in (carry, decay):
        assert not control["ok"]
        assert control["state"]["errors"]["kda_state"] > 1e-2
        # the first layer's convolution inputs come before any scan
        assert control["state"]["per_layer"]["conv_tail"][0] <= 1e-5
    # check.py's own numbers stay what every cell's line compares
    assert set(carry["errors"]) == set(sound["errors"])


def test_the_cells_runner_hands_its_comparison_to_the_loop_and_back():
    from benchmarks.runners import learner
    mix = harness.traffic_doc("learner-8k-state")
    plain, seen = learner.check, {}

    def loop(ctx):
        seen["check"] = learner.check.check_learner
        return "ran"

    original = learner_8k_state.learner_long.run
    learner_8k_state.learner_long.run = loop
    try:
        assert learner_8k_state.run(type("Ctx", (), {})) == "ran"
    finally:
        learner_8k_state.learner_long.run = original
    assert seen["check"] is learner_8k_state.check_learner
    assert learner.check is plain is check
    assert learner_8k_state.load_program is learner.load_program
    assert mix["runner"] == "learner_8k_state"


# -- the count of operations --------------------------------------------------


def test_count_of_operations_is_the_hand_count_at_a_small_size():
    """Layers [KDA + dense, MLA + experts], d = 8; KDA 2 heads of 4 in
    chunks of 4; MLA 2 heads, latent 4, qk 4 + 2, v 4, 2 stored slots; a
    window of 4, batch 3: every product written out."""
    from r2d2_tpu.config import Config
    cfg = Config().replace(**{
        "env.frame_stack": 1, "env.frame_height": 6, "env.frame_width": 6,
        "network.conv_layers": ((2, 3, 1),), "network.cnn_out_dim": 5,
        "network.hidden_dim": 7, "network.use_dueling": True,
        "network.use_double": True, "sequence.burn_in_steps": 1,
        "sequence.learning_steps": 2, "sequence.forward_steps": 1,
        "replay.batch_size": 3, "replay.block_length": 4,
        "replay.capacity": 16,
        "network.core.kind": "kda_mla_moe", "network.core.hidden_size": 8,
        "network.core.num_hidden_layers": 2,
        "network.core.layer_types": ("kda", "full_attention"),
        "network.core.num_attention_heads": 2,
        "network.core.kv_lora_rank": 4, "network.core.qk_nope_head_dim": 4,
        "network.core.qk_rope_head_dim": 2, "network.core.v_head_dim": 4,
        "network.core.kda_num_heads": 2, "network.core.kda_head_dim": 4,
        "network.core.short_conv_kernel_size": 4,
        "network.core.kda_chunk_size": 4, "network.core.mla_use_nope": True,
        "network.core.first_k_dense_replace": 1,
        "network.core.intermediate_size": 5,
        "network.core.moe_intermediate_size": 6,
        "network.core.n_routed_experts": 4, "network.core.n_shared_experts": 1,
        "network.core.num_experts_per_tok": 2,
        "network.core.experts_held": 1, "network.core.memory_len": 2})
    parts = costs_kimi.core_macs_per_position(cfg.network.core, 5 + 3, 4)
    assert parts == {
        "input_proj": 8 * 8,
        # q, k, v 8x8 each; the decay's 8x4 and 4x8; beta 8x2
        "kda_proj": 3 * 64 + 32 + 32 + 16,
        # per head: 3 state products 4x4, and 2 x chunk 4 x 4 for the pairs
        "kda_scan": 2 * (3 * 16 + 2 * 4 * 4),
        # the gate's 8x4 and 4x8; W_o 8x8
        "kda_out": 32 + 32 + 64,
        # q 8x2x6, kv_a 8x6; the expansion of (2 + 4) / 4 keys 4x2x8; scores
        # and values over 2 + 2.5 keys, 2 heads x (4 + 2 + 4); o 2x4x8
        "mla_attn": 96 + 48 + 1.5 * 64 + 4.5 * 2 * 10 + 64,
        "dense_mlp": 3 * 8 * 5,
        "moe_router": 8 * 4,
        "moe_shared": 3 * 8 * 6,
        # 2 choices x 1 of 4 experts held = half a pair a position
        "moe_experts": 0.5 * 3 * 8 * 6,
    }
    first_conv = 16 * 9 * 2
    outer = first_conv + 32 * 5 + 2 * 8 * 7 + 7 * 4
    assert costs_kimi.step_flops(cfg, 3) == pytest.approx(
        2 * 3 * 4 * ((outer + sum(parts.values())) * 4 - first_conv))
    # the kernels' own: one chunk a window of 4, 3 windows, one KDA layer;
    # two forwards (online, target) and a backward
    forward = 2 * (3 * 16 + 2 * 4 * 4)
    backward = 2 * (7 * 16 + 3.5 * 4 * 4)
    assert costs_kimi.kda_scan_flops(cfg) == pytest.approx(
        2 * 12 * (2 * forward + backward))
    per = 2 * 4
    states = 2 * 16 * 4 / 4
    assert costs_kimi.kda_scan_bytes(cfg) == pytest.approx(12 * (
        2 * (per * (3 * 2 + 8) + 2 * 4 + states)
        + per * (3 * 2 + 8 + 3 * 2 + 4) + 2 * 2 * 4 + states))


def test_the_cells_count_is_the_sizing_arithmetic():
    """The count by hand: about 688 MFLOP a position forward, KDA near half
    of it; the scan a few per cent of the step."""
    cfg = harness.build_config(harness.program_overrides(
        CONFIG, harness.traffic_doc("learner-8k-state")), "unused", 0)
    assert cfg.sequence.seq_len == 8192
    parts = costs_kimi.core_macs_per_position(cfg.network.core, 1030, 8192)
    forward = 2 * sum(parts.values())
    assert 640e6 < forward < 720e6
    kda = 2 * (parts["kda_proj"] + parts["kda_scan"] + parts["kda_out"])
    assert 0.45 < kda / forward < 0.52
    step = costs_kimi.step_flops(cfg, ACTION_DIM)
    assert 44e12 < step < 47e12
    assert 0.01 < costs_kimi.kda_scan_flops(cfg) / step < 0.05
    assert harness.costs_of(CONFIG) is costs_kimi


# -- the files ----------------------------------------------------------------


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    """Every key of the catalog's config stands at the top of the file with
    the catalog's value, but the depth, which is as run and listed in
    ``reduced``; the program's overrides say the same; no width is cut."""
    kda_layers = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                  22, 23, 25, 26]
    source = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": kda_layers, "num_heads": 32,
            "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840}
    differs = {k for k, v in source.items() if CONFIG.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} <= set(CONFIG["reduced"])
    assert CONFIG["num_hidden_layers"] == 5
    assert CONFIG["published"] == {"num_hidden_layers": 27,
                                   "experts_held": 256}
    assert CONFIG["reduced"] == [
        "network.core.num_hidden_layers", "network.core.experts_held",
        "replay.capacity", "num_hidden_layers"]
    overrides = CONFIG["overrides"]
    core = {k.split(".")[-1]: v for k, v in overrides.items()
            if k.startswith("network.core.")}
    linear = source["linear_attn_config"]
    # the layers held, 1-5, by the source's lists
    held = ["kda" if i in kda_layers else "full_attention"
            for i in range(1, 6)]
    assert core == {
        "kind": "kda_mla_moe", "hidden_size": 2304,
        "num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "mla_use_nope": True, "rope_theta": 10000, "rms_norm_eps": 1e-05,
        "intermediate_size": 9216, "moe_intermediate_size": 1024,
        "first_k_dense_replace": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_experts_per_tok": 8,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
        "kda_num_heads": linear["num_heads"],
        "kda_head_dim": linear["head_dim"],
        "short_conv_kernel_size": linear["short_conv_kernel_size"],
        "kda_chunk_size": 64, "q_chunk_size": 256, "num_hidden_layers": 5,
        "layer_types": held, "experts_held": 8, "expert_offset": 0,
        "memory_len": 128}
    assert CONFIG["experts_held"] == 8
    assert "thirty-two chips share each layer" in CONFIG["deployment"]
    assert (overrides["sequence.burn_in_steps"],
            overrides["sequence.learning_steps"],
            overrides["sequence.forward_steps"],
            overrides["replay.block_length"]) == (40, 8147, 5, 8147)
    assert overrides["replay.capacity"] == 12 * 8147
    assert overrides["replay.batch_size"] == 2
    for part in ("network.core.kda_chunk_size", "kda_low_rank", "kda_init",
                 "router", "router_input", "mla_use_nope", "head_dim",
                 "input_projection"):
        assert part in CONFIG["assumed"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "kimi-linear-core")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert entry["reduced"] == CONFIG["reduced"]
    assert (CONFIG["reference"], CONFIG["scopes"], CONFIG["costs"]) == (
        "r2d2_kimi", "r2d2_kimi", "costs_kimi")


def test_the_cells_size_is_the_files_arithmetic():
    """The parameters and the row the sizing block states (CPU, count)."""
    from r2d2_tpu.learner.train_step import create_train_state
    from r2d2_tpu.models.cores import state_half
    from r2d2_tpu.models.network import NetworkApply
    cfg = harness.build_config(harness.program_overrides(
        CONFIG, harness.traffic_doc("learner-8k-state")), "unused", 0)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    train = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), net, cfg.optim))
    count = sum(x.size for x in jax.tree_util.tree_leaves(train.params))
    assert f"{count:,}" in CONFIG["sizing"]["parameters"]
    assert 2 * state_half(cfg.network) == 2_318_336
    assert "2,318,336" in CONFIG["sizing"]["state_row"]
    assert harness.traffic_doc("learner-8k-state")["parameters"]["replay"][
        "pool_blocks"] <= 12


def test_the_cell_joins_the_lists_and_brings_its_own_readers():
    bm_structure.cell_resolves(BENCH, CELL)
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-core", "learner-8k-state", 1)
    assert len(cell["why"]) <= 200
    for name in JOINED:
        assert bm_structure.in_order(
            [bm_structure.MOONLIGHT, "keye-core.learner-8k", CELL],
            bm_structure.metric(BENCH, name)["workloads"]), name
    assert CELL not in bm_structure.metric(BENCH, "lstm_self_share")[
        "workloads"]
    names = bm_structure.per_layer_names(BENCH)
    assert bm_structure.contiguous(
        ["keye-core.moe_dispatch_self_share"] + list(READERS), names)
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
    for name in list(READERS)[2:]:
        assert harness.reader_of(name) is harness.reader_of(
            name.split(".", 1)[1])
    mix = harness.traffic_doc("learner-8k-state")
    assert mix["runner"] == "learner_8k_state"
    assert mix["overrides"]["runtime.steps_per_dispatch"] == 1
    assert mix["parameters"]["subwindow_dispatches"] == 2
    assert mix["parameters"]["check_sequences"] == 2


def test_scope_table_puts_kdas_scopes_first():
    table = harness.scope_table(CONFIG)
    tokens = [token for token, _ in table]
    assert tokens[:4] == ["ragged-dot", "kda_proj", "kda_scan", "kda_out"]
    moonlight = harness.scope_table(harness.config_doc(BENCH,
                                                       "moonlight-core"))
    assert table[4:] == moonlight[1:]
    for a in tokens:
        assert not any(a != b and a in b for b in tokens), a


def test_rehearsal_names_only_keys_of_the_program():
    from r2d2_tpu.config import CoreConfig
    names = {f.name for f in dataclasses.fields(CoreConfig)}
    for part in ("overrides", "rehearsal"):
        keys = {k.split(".")[-1] for k in CONFIG[part]
                if k.startswith("network.core.")}
        assert keys <= names, keys - names
    json.dumps(CONFIG)


# -- the readers --------------------------------------------------------------


def _cfg(config_name, mix, save_dir="unused"):
    return harness.build_config(harness.program_overrides(
        harness.config_doc(BENCH, config_name), harness.traffic_doc(mix)),
        save_dir, 0)


@pytest.mark.parametrize("reader", list(READERS))
def test_new_readers_find_nothing_in_a_program_without_the_core(reader,
                                                                tmp_path):
    """The parent's program has none of the scopes and runs none of the
    kernels: on its capture (the recorded r2d2-ref.learner fixture) a new
    reader returns nothing and does not raise, with or without a trace."""
    import glob

    from benchmarks.trace import reduce, xspace_text
    (path,) = glob.glob(os.path.join(harness.BENCH_DIR, "trace", "fixtures",
                                     "*.txt.gz"))
    summary = reduce.summarize_data(xspace_text.load(path),
                                    scopes=harness.scope_table(CONFIG))
    assert summary.busy_s() > 0
    for trace in (summary, None):
        ctx = run.MetricContext(
            cfg=_cfg("r2d2-ref", "learner", str(tmp_path)), values={},
            trace=trace, device_kind="TPU v5 lite", config=CONFIG,
            facts={"steps_per_dispatch": 16, "action_dim": ACTION_DIM})
        got = harness.reader_of(reader).read(ctx)
        if reader.endswith("core_self_share") and trace is not None:
            # the LSTM's program has no mem_core scope either
            assert got is None or got >= 0
        else:
            assert got is None


STEP = "jit(multi_step)/jit(main)/while/body/"
CORE = STEP + "R2D2Network/mem_core/"


def _capture(steps=2):
    """``steps`` train steps in one program, each: KDA's projections
    (1,000 ns), the scan's forward kernel (2,000) and backward kernel
    (3,000), the gated output (500), the latent attention (1,500), the
    experts' grouped products as the chip names them (1,000), the loss
    (250)."""
    from jax.profiler import ProfileData

    from benchmarks.trace import reduce, xspace_text

    def op(name, start, dur, path, kernel=False):
        text = (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
                "custom_call_target=\"tpu_custom_call\"" if kernel else
                f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %p)")
        return (text, start, dur, {"op_name": path})
    attn = CORE + "layers_0/self_attn/"
    rows = [("fusion", 1000, attn + "kda_proj/dot_general", False),
            ("kda_scan_fwd", 2000, attn + "kda_scan/pallas_call", True),
            ("kda_scan_bwd", 3000,
             "transpose(jvp(" + attn + "kda_scan/pallas_call))", True),
            ("fusion", 500, attn + "kda_out/dot_general", False),
            ("fusion", 1500, CORE + "layers_3/mla_attn/self_attn/exp",
             False),
            ("ragged-dot-none", 1000, "ragged-dot-none", False),
            ("fusion", 250, STEP + "loss/sub", False)]
    ops, at = [], 1000
    for i in range(steps):
        for j, (name, dur, path, kernel) in enumerate(rows):
            ops.append(op(f"{name}.{i}{j}", at, dur, path, kernel))
            at += dur
    capture = {"/device:TPU:0": {
        "XLA Modules": [("jit_multi_step(1)", 1000, at - 1000, {})],
        "XLA Ops": ops}}
    return reduce.summarize_data(
        ProfileData.from_text_proto(xspace_text.to_text(capture)),
        harness.scope_table(CONFIG))


def test_the_cells_readers_read_the_made_up_rows(tmp_path):
    summary = _capture()
    busy = summary.busy_s()
    assert busy == pytest.approx(2 * 9250e-9)
    cfg = _cfg("kimi-linear-core", "learner-8k-state", str(tmp_path))
    ctx = run.MetricContext(
        cfg=cfg, values={}, trace=summary, device_kind="TPU v5 lite",
        config=CONFIG, facts={"steps_per_dispatch": 2,
                              "action_dim": ACTION_DIM})
    for reader, ns in (("kda_self_share", 6500),
                       ("kimi-linear-core.mla_self_share", 1500),
                       ("kimi-linear-core.core_self_share", 9000)):
        assert harness.reader_of(reader).read(ctx) == pytest.approx(
            100 * 2 * ns * 1e-9 / busy), reader
    # the one program ran 2 steps: the least time of 2 steps' counted work
    # over the 10,000 ns the kernels took
    least = 2 * max(costs_kimi.kda_scan_flops(cfg) / 197e12,
                    costs_kimi.kda_scan_bytes(cfg) / 819e9)
    got = harness.reader_of("k_kda_scan_roofline").read(ctx)
    assert got == pytest.approx(100 * least / (2 * 5000e-9))
