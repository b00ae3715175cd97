"""The ``keye-core`` configuration: the program's ``sparse_attn_moe`` core
against the plain reference (forward, TD loss, the indexer's loss and their
gradients, float32 and bf16, through ``check.compare``), where the queries
choose (more keys than ``index_topk``) and where they do not (plain
grouped-query attention); the benchmark's count of operations against a hand
count; the files' contract with the catalog; the cell's entries in
``BENCHMARK.json``; and the new readers, on a made-up capture and on a
program without the core. Tiny sizes, CPU, seeded random weights."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_structure
from benchmarks import costs_keye, harness, run, traffic
from benchmarks.reference import check, r2d2_keye
from benchmarks.runners import learner_8k

BENCH = harness.load_benchmark()
CELL = "keye-core.learner-8k"
CONFIG = harness.config_doc(BENCH, "keye-core")
POOL = {"pool_blocks": 4, "priority_range": [0.1, 2.0], "reward_scale": 1.0}
ACTION_DIM = 6
# the cell's name at the end of these lists
JOINED = ["seq_updates_per_s", "dispatch_host_ms", "train_step_ms",
          "mfu_bf16", "torso_self_share", "k_decode_roofline",
          "k_gather_roofline", "device_idle_share", "setup_trace_lower_s",
          "setup_backend_s", "setup_first_run_s", "setup_build_s"]
# the cell's own per-layer entries, in their order: (layer, better)
READERS = {"dsa_self_share": ("memory_core", "lower"),
           "indexer_self_share": ("memory_core", "lower"),
           "k_sparse_attn_roofline": ("kernels", "higher"),
           "keye-core.core_self_share": ("memory_core", "lower"),
           "keye-core.moe_self_share": ("memory_core", "lower"),
           "keye-core.k_experts_roofline": ("kernels", "higher"),
           "keye-core.gqa_self_share": ("memory_core", "lower"),
           "keye-core.moe_dispatch_self_share": ("memory_core", "lower")}
# the twin's sizes with its layers routed, and a choice that does the work:
# a window of 25 over 4 stored positions sees up to 29 keys and chooses 8
ROUTED = {"network.core.first_k_dense_replace": 0}
NO_CHOICE = {"network.core.index_topk": 64}


def _tiny_cfg(tmp_path, seed=0, **extra):
    overrides = harness.program_overrides(CONFIG, {}, rehearse=True)
    return harness.build_config({**overrides, "runtime.save_interval": 0,
                                 "runtime.steps_per_dispatch": 2, **ROUTED,
                                 **extra}, str(tmp_path), seed)


def _tiny_learner(tmp_path, seed=0, **extra):
    """The configuration's tiny CPU twin, its layers routed, on a full ring
    (every sequence's stored row random), a target net that differs from
    the online one."""
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


# float32 on both sides, one backend: they differ in the order of summation,
# in a threshold against an index set, and in the grouped product against
# the per-expert loop, about 1e-6
@pytest.mark.parametrize("choice", [{}, NO_CHOICE],
                         ids=["top_8_of_29", "no_choice"])
def test_program_loss_matches_reference_in_float32(tmp_path, choice):
    learner = _tiny_learner(tmp_path, **choice)
    try:
        out = check.check_learner(learner, "r2d2_keye", 8, seed=7)
    finally:
        learner.stop_background()
    assert out["compute_dtype"] == "float32" and out["tolerance"] == 5e-5
    assert out["ok"], out
    assert out["valid_steps"] > 0 and out["stable_steps"] > 0


def test_with_no_more_keys_than_topk_it_is_plain_grouped_query_attention():
    """Where every query sees at most ``index_topk`` keys the layer is
    grouped-query attention over every visible key, whatever the indexer
    scores: its weights moved, the output does not."""
    from r2d2_tpu.models.cores.sparse_attn_moe import SparseAttention
    cfg = _tiny_cfg("unused", **NO_CHOICE)
    core = cfg.network.core
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 25, core.hidden_size))
    stored = jax.random.normal(jax.random.PRNGKey(1), (2, core.memory_len,
                                                      r2d2_keye.row_width(
                                                          dataclasses.asdict(
                                                              core))))
    module = SparseAttention(core, jnp.float32, True)
    params = module.init(jax.random.PRNGKey(2), h, stored)["params"]
    out, _, _ = module.apply({"params": params}, h, stored)
    moved = {**params, "indexer": jax.tree_util.tree_map(
        lambda x: x + 1.0, params["indexer"])}
    again, _, _ = module.apply({"params": moved}, h, stored)
    np.testing.assert_array_equal(out, again)
    # and the reference's own dense softmax over every visible key
    ref_out, _, chosen = r2d2_keye._attention(h, stored, params,
                                              dataclasses.asdict(core))
    np.testing.assert_allclose(out, ref_out, atol=2e-6)
    assert bool(chosen.all(axis=-1)[:, -1].all())


def test_bf16_program_passes_its_tolerance_and_fails_float32s(tmp_path):
    learner = _tiny_learner(tmp_path, **{"network.bf16": "on"})
    try:
        program, reference, weights, dtype = check.program_and_reference(
            learner, "r2d2_keye", 8, seed=7)
    finally:
        learner.stop_background()
    assert dtype == "bfloat16"
    assert check.compare(program, reference, weights, 5e-2)["ok"]
    assert not check.compare(program, reference, weights, 5e-5)["ok"]


@pytest.mark.parametrize("choice", [{}, NO_CHOICE],
                         ids=["top_8_of_29", "no_choice"])
def test_indexer_loss_and_the_gradients_match_the_references(tmp_path,
                                                             choice):
    """The program's gradient is of the TD loss and the indexer's; the
    reference's of its ``loss`` and its ``indexer_loss``. The program's
    step reports the TD loss as its loss and the indexer's per layer."""
    from r2d2_tpu.learner.train_step import make_loss_fn
    cfg, net, spec, batch, params, target = _sampled(tmp_path, **choice)
    loss_fn = make_loss_fn(net, spec, cfg.optim, True)
    value, aux = loss_fn(params, target, batch)
    fields = _fields(batch)
    reference = r2d2_keye.from_config(cfg)

    def reference_loss(p):
        """The reference's TD loss and its indexer loss (the sum of the
        layers' means over the learning positions)."""
        out = reference(p, target, fields)
        return out["loss"], jnp.sum(r2d2_keye.learning_mean(
            out["indexer_kl"], fields))

    want_td, want_index = reference_loss(params)
    np.testing.assert_allclose(value, want_td, rtol=2e-5)
    np.testing.assert_allclose(jnp.sum(aux["core"]["loss"]),
                               want_index, rtol=2e-5, atol=1e-7)
    assert float(want_index) > 0
    program = jax.grad(lambda p: loss_fn(p, target, batch)[0])(params)
    wanted = jax.grad(lambda p: sum(reference_loss(p)))(params)
    flat_p = jax.tree_util.tree_leaves_with_path(program)
    flat_r = jax.tree_util.tree_leaves(wanted)
    assert len(flat_p) == len(flat_r) > 30
    kinds = set()
    for (path, got), want in zip(flat_p, flat_r):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(want).max())
        if "router_input_mean" in name:
            assert scale == 0.0 and float(jnp.abs(got).max()) == 0.0
            continue
        assert scale > 0, path
        np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0,
                                   err_msg=name)
        kinds |= {k for k in ("'indexer'", "'self_attn'", "'experts'")
                  if k in name}
    assert kinds == {"'indexer'", "'self_attn'", "'experts'"}


def test_the_references_choice_and_the_programs_agree(tmp_path):
    """In float32 the program's threshold chooses the reference's index
    set, key for key, in every layer of the online window, and its
    indexer's KL at each position is the reference's."""
    cfg, net, spec, batch, params, target = _sampled(tmp_path)
    outputs = r2d2_keye.from_config(cfg)(params, target, _fields(batch))
    want, kl = outputs["chosen"], outputs["indexer_kl"]
    got, got_kl = learner_8k.program_choice(net, spec, params, batch, False)
    assert got.shape == want.shape
    assert len(got) == cfg.network.core.num_hidden_layers
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_kl, kl, rtol=2e-5, atol=1e-7)
    # queries from window step 4 on see more than 8 keys and choose 8
    assert int(np.asarray(want[0]).sum(-1).max()) == 8


def test_the_cells_comparison_holds_the_choice_and_fails_its_controls(
        tmp_path):
    """The cell's runner widens ``check.py``'s comparison by the choice,
    and reads the indexer's loss beside it: the float32 twin chooses the
    reference's pairs, a program that attends every key it sees, or whose
    indexer's projections are coarsened, fails the comparison on what
    ``check.py`` alone lets through."""
    learner = _tiny_learner(tmp_path)
    try:
        program, reference, weights, dtype = check.program_and_reference(
            learner, "r2d2_keye", 4, seed=3)
        batch = check.sample_sequences(learner, 4, seed=3)
        cfg, net, params = learner.cfg, learner.net, jax.device_get(
            learner.train_state.params)
        spec = dataclasses.replace(learner.spec, batch_size=4)
    finally:
        learner.stop_background()
    fields = _fields(batch)
    base = check.compare(program, reference, weights, 5e-5)
    assert base["ok"]

    def widened(network, p=params):
        chosen, kl = learner_8k.program_choice(network, spec, p, batch,
                                               False)
        return learner_8k.widen(base, learner_8k.compare_choice(
            chosen, kl, reference, fields, dtype))

    out = widened(net)
    assert out["ok"], out
    found = out["choice"]
    assert found["errors"] == {"choice": 0.0}
    assert found["limits"] == {"choice": 1e-3}
    assert found["choice_agreement"] == [1.0, 1.0]
    assert found["indexer_loss_error"] <= 1e-4
    # check.py's own numbers stay what every cell's line compares
    assert out["errors"] == base["errors"] and out["limits"] == base["limits"]
    from r2d2_tpu.models.network import NetworkApply
    every_key = NetworkApply(ACTION_DIM, dataclasses.replace(
        cfg.network, core=dataclasses.replace(cfg.network.core,
                                              index_topk=1 << 20)),
        cfg.env.frame_stack, cfg.env.frame_height, cfg.env.frame_width)
    off = widened(every_key)
    assert not off["ok"] and off["choice"]["errors"]["choice"] > 0.5
    assert off["choice"]["indexer_loss_error"] > 1e-4
    # the indexer's projections rounded to 8-bit floats move its choice
    rounded = jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if "'indexer'" in jax.tree_util.keystr(path) and x.ndim > 1 else x,
        params)
    coarse = widened(net, rounded)
    assert not coarse["ok"] and coarse["choice"]["errors"]["choice"] > 1e-3


def test_the_cells_runner_hands_its_comparison_to_the_loop_and_back():
    from benchmarks.runners import learner
    mix = harness.traffic_doc("learner-8k")
    plain, seen = learner.check, {}

    def loop(ctx):
        seen["check"] = learner.check.check_learner
        return "ran"

    original = learner_8k.learner_long.run
    learner_8k.learner_long.run = loop
    try:
        assert learner_8k.run(type("Ctx", (), {})) == "ran"
    finally:
        learner_8k.learner_long.run = original
    assert seen["check"] is learner_8k.check_learner
    assert learner.check is plain is check
    assert learner_8k.load_program is learner.load_program
    assert mix["runner"] == "learner_8k"


# -- the count of operations ---------------------------------------------------


def test_count_of_operations_is_the_hand_count_at_a_small_size():
    """Two expert layers, d = 8, 2 query heads of 4 on 1 key/value head, an
    indexer of 2 heads of 2 choosing 3, a window of 4 over 2 stored
    positions, batch 3: every product written out."""
    from r2d2_tpu.config import Config
    cfg = Config().replace(**{
        "env.frame_stack": 1, "env.frame_height": 6, "env.frame_width": 6,
        "network.conv_layers": ((2, 3, 1),), "network.cnn_out_dim": 5,
        "network.hidden_dim": 7, "network.use_dueling": True,
        "network.use_double": True, "sequence.burn_in_steps": 1,
        "sequence.learning_steps": 2, "sequence.forward_steps": 1,
        "replay.batch_size": 3, "replay.block_length": 4,
        "replay.capacity": 16,
        "network.core.kind": "sparse_attn_moe", "network.core.hidden_size": 8,
        "network.core.num_attention_heads": 2,
        "network.core.num_key_value_heads": 1, "network.core.head_dim": 4,
        "network.core.index_n_heads": 2, "network.core.index_head_dim": 2,
        "network.core.index_topk": 3, "network.core.q_chunk_size": 4,
        "network.core.kv_chunk_size": 4, "network.core.num_hidden_layers": 2,
        "network.core.first_k_dense_replace": 0,
        "network.core.moe_intermediate_size": 6,
        "network.core.n_routed_experts": 4,
        "network.core.num_experts_per_tok": 2,
        "network.core.experts_held": 1, "network.core.memory_len": 2,
        "network.core.scoring_func": "softmax"})
    # positions see 3, 4, 5, 6 keys and choose 3, 3, 3, 3
    assert costs_keye.keys_per_position(cfg.network.core, 4) == (4.5, 3.0)
    parts = costs_keye.core_macs_per_position(cfg.network.core, 5 + 3, 4)
    assert parts == {
        "input_proj": 8 * 8,
        # q 8x8, k and v 8x4 each, o 8x8
        "gqa_attn": 2 * (64 + 32 + 32 + 64),
        # qI 8x4, kI 8x2, weights 8x2; 4.5 keys x 2 heads x 2
        "dsa_indexer": 2 * (32 + 16 + 16 + 4.5 * 2 * 2),
        # 3 keys x 2 heads x (4 + 4)
        "dsa_attn": 2 * 3 * 2 * 8,
        "dense_mlp": 0,
        "moe_router": 2 * 8 * 4,
        # 2 choices x 1 of 4 experts held = half a pair a position
        "moe_experts": 2 * 0.5 * 3 * 8 * 6,
    }
    first_conv = 16 * 9 * 2
    outer = first_conv + 32 * 5 + 2 * 8 * 7 + 7 * 4
    assert costs_keye.step_flops(cfg, 3) == pytest.approx(
        2 * 3 * 4 * ((outer + sum(parts.values())) * 4 - first_conv))
    # a chosen pair: its score and weighted value in 2 heads of 4, 4 passes
    assert costs_keye.sparse_attn_flops(cfg, 1) == 4 * 2 * 4 * 4


def test_the_cells_count_is_the_sizing_arithmetic():
    cfg = harness.build_config(harness.program_overrides(
        CONFIG, harness.traffic_doc("learner-8k")), "unused", 0)
    assert cfg.sequence.seq_len == 8192
    parts = costs_keye.core_macs_per_position(cfg.network.core, 1030, 8192)
    forward = 2 * sum(parts.values())
    # ~91 MFLOP a position and layer
    assert 4 * 90e6 < forward < 4 * 93e6
    step = costs_keye.step_flops(cfg, ACTION_DIM)
    assert 25e12 < step < 26.5e12
    seen, chosen = costs_keye.keys_per_position(cfg.network.core, 8192)
    assert chosen < 2048 < seen
    assert harness.costs_of(CONFIG) is costs_keye


# -- the files -----------------------------------------------------------------


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    """Every key of the catalog's config stands at the top of the file with
    the catalog's value, but the depth, which is as run and listed in
    ``reduced``; the program's overrides say the same; no width is cut."""
    source = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in source.items() if CONFIG.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} <= set(CONFIG["reduced"])
    assert CONFIG["num_hidden_layers"] == 4
    assert CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["reduced"] == [
        "network.core.num_hidden_layers", "network.core.experts_held",
        "replay.capacity", "num_hidden_layers"]
    overrides = CONFIG["overrides"]
    core = {k.split(".")[-1]: v for k, v in overrides.items()
            if k.startswith("network.core.")}
    sa = source["sa_config"]
    assert core == {
        "kind": "sparse_attn_moe", "hidden_size": 2048,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "rope_theta": 10000000, "rms_norm_eps": 1e-06,
        "intermediate_size": 6144, "moe_intermediate_size": 768,
        "first_k_dense_replace": 0, "n_routed_experts": 128,
        "num_experts_per_tok": 8, "routed_scaling_factor": 1.0,
        "scoring_func": "softmax",
        "index_n_heads": sa["indexer_num_heads"],
        "index_head_dim": sa["indexer_head_dim"], "index_topk": sa["topk"],
        "q_chunk_size": sa["q_chunk_size"],
        "kv_chunk_size": sa["kv_chunk_size"], "num_hidden_layers": 4,
        "experts_held": 16, "expert_offset": 0, "memory_len": 128}
    assert CONFIG["experts_held"] == 16
    assert "eight chips share each layer" in CONFIG["deployment"]
    # the cell's window and ring: 40 + 8,147 + 5, one sequence a block
    assert (overrides["sequence.burn_in_steps"],
            overrides["sequence.learning_steps"],
            overrides["sequence.forward_steps"],
            overrides["replay.block_length"]) == (40, 8147, 5, 8147)
    assert overrides["replay.capacity"] == 12 * 8147
    assert overrides["replay.batch_size"] == 2
    for part in ("indexer", "indexer_loss", "router_input", "rope",
                 "indexer_dtype", "input_projection"):
        assert part in CONFIG["assumed"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "keye-core")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert entry["reduced"] == CONFIG["reduced"]
    assert (CONFIG["reference"], CONFIG["scopes"], CONFIG["costs"]) == (
        "r2d2_keye", "r2d2_keye", "costs_keye")


def test_the_cells_size_is_the_files_arithmetic():
    """The parameters and the row the sizing block states (CPU, count)."""
    from r2d2_tpu.learner.train_step import create_train_state
    from r2d2_tpu.models.cores import state_half
    from r2d2_tpu.models.network import NetworkApply
    cfg = harness.build_config(harness.program_overrides(
        CONFIG, harness.traffic_doc("learner-8k")), "unused", 0)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    train = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), net, cfg.optim))
    count = sum(x.size for x in jax.tree_util.tree_leaves(train.params))
    assert f"{count:,}" in CONFIG["sizing"]["core"]
    assert 2 * state_half(cfg.network) == 557_056
    traffic_doc = harness.traffic_doc("learner-8k")
    assert traffic_doc["parameters"]["replay"]["pool_blocks"] <= 12


def test_the_cell_joins_the_lists_and_brings_its_own_readers():
    bm_structure.cell_resolves(BENCH, CELL)
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-core", "learner-8k", 1)
    assert len(cell["why"]) <= 200
    for name in JOINED:
        assert bm_structure.metric(BENCH, name)["workloads"][-1] == CELL \
            or CELL in bm_structure.metric(BENCH, name)["workloads"], name
        assert bm_structure.in_order(
            [bm_structure.MOONLIGHT, CELL],
            bm_structure.metric(BENCH, name)["workloads"]), name
    assert CELL not in bm_structure.metric(BENCH, "lstm_self_share")[
        "workloads"]
    names = bm_structure.per_layer_names(BENCH)
    assert bm_structure.contiguous(["setup_build_s"] + list(READERS), names)
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
    for name in list(READERS)[3:]:
        assert harness.reader_of(name) is harness.reader_of(
            name.split(".", 1)[1])
    mix = harness.traffic_doc("learner-8k")
    assert mix["runner"] == "learner_8k"
    # one train step a dispatch, sub-windows of two dispatches
    assert mix["overrides"]["runtime.steps_per_dispatch"] == 1
    assert mix["parameters"]["subwindow_dispatches"] == 2
    assert mix["parameters"]["replay"]["pool_blocks"] <= 12


def test_scope_table_puts_the_sparse_attentions_scopes_first():
    table = harness.scope_table(CONFIG)
    tokens = [token for token, _ in table]
    assert tokens[:12] == ["ragged-dot", "dsa_indexer", "dsa_select",
                           "dsa_attn", "dsa_kl", "gqa_attn", "dense_mlp",
                           "moe_router", "moe_dispatch", "moe_experts",
                           "moe_combine", "mem_core"]
    r2d2 = harness.scope_table(harness.config_doc(BENCH, "r2d2-paper"))
    assert table[12:] == r2d2
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


# -- the readers ---------------------------------------------------------------


def _cfg(config_name, mix, save_dir="unused"):
    return harness.build_config(harness.program_overrides(
        harness.config_doc(BENCH, config_name), harness.traffic_doc(mix)),
        save_dir, 0)


@pytest.mark.parametrize("reader", list(READERS))
def test_new_readers_find_nothing_in_a_program_without_the_core(reader,
                                                                tmp_path):
    """The parent's program has none of the scopes, runs none of the
    kernels and counts no chosen pairs: on its capture (the recorded
    r2d2-ref.learner fixture) a new reader returns nothing and does not
    raise, with or without a trace."""
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
        assert harness.reader_of(reader).read(ctx) is None


STEP = "jit(multi_step)/jit(main)/while/body/"
ATTN = STEP + "R2D2Network/mem_core/layers_0/self_attn/"


def _capture(steps=2):
    """``steps`` train steps in one program, each: the attention's
    projections (1,000 ns), the indexer's scores as a kernel (1,500), the
    threshold (500), the sparse attention's kernel forward (2,000) and
    backward (3,000), the heads' probabilities (500), the experts' grouped
    products as the chip names them (1,000), the router (250), the loss
    (250)."""
    from jax.profiler import ProfileData

    from benchmarks.trace import reduce, xspace_text

    def op(name, start, dur, path, kernel=False):
        text = (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
                "custom_call_target=\"tpu_custom_call\"" if kernel else
                f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %p)")
        return (text, start, dur, {"op_name": path})
    rows = [("fusion", 1000, ATTN + "gqa_attn/dot_general", False),
            ("dsa_indexer", 1500, ATTN + "dsa_indexer/pallas_call", True),
            ("fusion", 500, ATTN + "dsa_select/pallas_call", False),
            ("sparse_attn", 2000, ATTN + "dsa_attn/pallas_call", True),
            ("sparse_attn_dq", 3000,
             "transpose(jvp(" + ATTN + "dsa_attn/pallas_call))", True),
            ("dsa_probs", 500, ATTN + "dsa_kl/pallas_call", True),
            ("ragged-dot-none", 1000, "ragged-dot-none", False),
            ("fusion", 250, STEP + "R2D2Network/mem_core/layers_0/mlp/"
             "moe_router/exp", False),
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
    assert busy == pytest.approx(2 * 10000e-9)
    # the program's last flush: 2 traced steps, 2 x 3,000 chosen pairs
    with open(tmp_path / "spans_player0.jsonl", "w") as f:
        for tags in ({"dsa_steps": 4, "dsa_pairs": 1}, {}, {
                "dsa_steps": 2, "dsa_pairs": 6000}):
            f.write(json.dumps({"name": "learner/diag_flush", "ts": 0.0,
                                "dur": 0.001, "tid": "MainThread",
                                "tags": tags}) + "\n")
    ctx = run.MetricContext(
        cfg=_cfg("keye-core", "learner-8k", str(tmp_path)), values={},
        trace=summary, device_kind="TPU v5 lite", config=CONFIG,
        facts={"steps_per_dispatch": 2, "action_dim": ACTION_DIM,
               "moe_traced": {"steps": 2, "pairs_held": 8,
                              "rows_walked": 12}})
    for reader, ns in (("dsa_self_share", 7500),
                       ("indexer_self_share", 2500),
                       ("keye-core.moe_self_share", 1250),
                       ("keye-core.gqa_self_share", 1000)):
        assert harness.reader_of(reader).read(ctx) == pytest.approx(
            100 * 2 * ns * 1e-9 / busy), reader
    # 3,000 pairs a step x 4 x 32 heads x 128 x 4 passes over the 5,000 ns a
    # step of the kernels under dsa_attn
    got = harness.reader_of("k_sparse_attn_roofline").read(ctx)
    assert got == pytest.approx(
        100 * 2 * 3000 * 4 * 32 * 128 * 4 / (2 * 5000e-9) / 197e12)
    assert 0 < got < 100
    # no flush that counted: nothing
    (tmp_path / "spans_player0.jsonl").write_text("")
    assert harness.reader_of("k_sparse_attn_roofline").read(ctx) is None
