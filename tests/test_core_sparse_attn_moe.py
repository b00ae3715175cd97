"""The ``sparse_attn_moe`` core's own cases (``r2d2_tpu/models/cores/
sparse_attn_moe.py``) and its kernels (``r2d2_tpu/ops/sparse_attention.py``):
the kernels against plain ``jax.numpy`` in interpret mode, forward and
backward; the exact top-k threshold and its ties; the configuration keys and
the state row; the actor's step against the window; the indexer learning
from its loss alone; the softmax router's shares against the uncut layer;
the sigmoid cores' programs left as they were; and the core on the normal
path (``cli.train``). The comparison with the plain reference is in
``tests/benchmarks/test_bm_keye.py``. Tiny sizes, CPU, seeded weights."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import Config, CoreConfig
from r2d2_tpu.models.cores import make_core, state_block, state_half
from r2d2_tpu.ops import sparse_attention as sa
from tests.test_cores import TINY_ENV, inputs, tiny_net

KIND = "sparse_attn_moe"
TINY_SPARSE_CORE = {
    "kind": KIND, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "experts_held": 4, "num_hidden_layers": 2,
    "first_k_dense_replace": 0, "memory_len": 4, "rope_theta": 1e7,
    "rms_norm_eps": 1e-6, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "index_n_heads": 2, "index_head_dim": 8,
    "index_topk": 8, "q_chunk_size": 8, "kv_chunk_size": 8}
TINY_REPLAY = {"sequence.burn_in_steps": 4, "sequence.learning_steps": 30,
               "sequence.forward_steps": 3, "replay.block_length": 30,
               "replay.capacity": 240, "replay.batch_size": 2}


def tiny_config(**core) -> Config:
    sizes = {f"network.core.{k}": v
             for k, v in {**TINY_SPARSE_CORE, **core}.items()}
    return Config().replace(**{**TINY_ENV, **TINY_REPLAY, **sizes})


# -- the kernels against plain jax.numpy, in interpret mode -----------------

B, G, N, T, S, E, BQ, BK = 2, 2, 3, 32, 48, 8, 16, 16
OFFSET = S - T          # the key at the window's first position


def _case(seed=0):
    """Queries, keys, values, a choice ``d`` over the keys each query sees
    (its own key always, a random 40% of the rest, none past it), and the
    visibility mask."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    pos = jnp.arange(S) - OFFSET
    visible = pos[None, :] <= jnp.arange(T)[:, None]
    pick = visible[None] & (jax.random.uniform(k[3], (B, T, S)) < 0.4)
    pick = pick | (pos[None, None, :] == jnp.arange(T)[None, :, None])
    d = jnp.where(pick, jax.random.uniform(k[4], (B, T, S)), -jnp.inf)
    return (jax.random.normal(k[0], (B, G * N, T, E)),
            jax.random.normal(k[1], (B, G, S, E)),
            jax.random.normal(k[2], (B, G, S, E)), d, visible, k[5])


def _close(got, want, atol=2e-6):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=atol * max(np.abs(want).max(), 1.0))


@pytest.mark.parametrize("part", ["forward", "backward", "probs", "indexer",
                                  "indexer_back", "select"])
def test_kernel_matches_the_dense_masked_form_in_interpret_mode(part):
    q, k, v, d, visible, key = _case()
    scale = E ** -0.5
    if part in ("forward", "backward", "probs"):
        o_ref, lse_ref = sa.attention_reference(q, k, v, d, scale)
        if part == "forward":
            o, lse = sa.sparse_attention_pallas(q, k, v, d, scale, BQ, BK,
                                                interpret=True)
            _close(o, o_ref)
            _close(lse, lse_ref)
        elif part == "backward":
            do = jax.random.normal(key, o_ref.shape)
            dlse = jax.random.normal(jax.random.fold_in(key, 1),
                                     lse_ref.shape)
            want = jax.vjp(lambda q, k, v: sa.attention_reference(
                q, k, v, d, scale), q, k, v)[1]((do, dlse))
            dl = jnp.sum(do * o_ref, -1) - dlse
            got = sa.sparse_attention_back_pallas(q, k, v, d, do, lse_ref, dl,
                                                  scale, BQ, BK,
                                                  interpret=True)
            for x, y in zip(got, want):
                _close(x, y)
        else:
            probs = sa.chosen_probs_pallas(q, k, lse_ref, d, scale, BQ, BK,
                                           interpret=True)
            _close(probs, sa.chosen_probs_reference(q, k, lse_ref, d, scale))
            # each query's probabilities over its chosen keys sum to one
            _close(probs.sum(-1), jnp.ones((B, T)))
        return
    j, c = 3, 8
    ks = jax.random.split(key, 4)
    qi = jax.random.normal(ks[0], (B, j, T, c))
    w = jax.random.normal(ks[1], (B, j, T))
    ki = jax.random.normal(ks[2], (B, S, c))
    scores = sa.indexer_reference(qi, w, ki)
    if part == "indexer":
        got = sa.indexer_pallas(qi, w, ki, OFFSET, BQ, BK, interpret=True)
        # the kernel skips key blocks past a query block's last key
        _close(jnp.where(visible, got, 0), jnp.where(visible, scores, 0))
    elif part == "indexer_back":
        g = jnp.where(visible, jax.random.normal(ks[3], scores.shape), 0)
        want = jax.vjp(sa.indexer_reference, qi, w, ki)[1](g)
        got = sa.indexer_back_pallas(qi, w, ki, g, OFFSET, BQ, BK,
                                     interpret=True)
        for x, y in zip(got, want):
            _close(x, y, 5e-6)
    else:
        shown = jnp.where(visible, scores, -jnp.inf)
        want_k = jnp.broadcast_to(jnp.minimum(visible.sum(-1), 5), (B, T))
        got = sa.kth_largest_pallas(shown, want_k, rows=8, interpret=True)
        for x, y in zip(got, sa.kth_largest_reference(shown, want_k, 5)):
            np.testing.assert_array_equal(x, y)


def test_the_custom_gradients_are_the_dense_forms():
    """``sparse_attention`` and ``indexer_scores`` carry their own backward;
    off the TPU both take the plain forms, whose gradients they must give."""
    q, k, v, d, visible, key = _case(1)
    scale = E ** -0.5

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for x, y in zip(loss(lambda q, k, v: sa.sparse_attention(
            q, k, v, d, scale, BQ, BK)), loss(lambda q, k, v:
                                              sa.attention_reference(
                                                  q, k, v, d, scale))):
        _close(x, y)
    ks = jax.random.split(key, 3)
    qi, w, ki = (jax.random.normal(ks[0], (B, 2, T, 4)),
                 jax.random.normal(ks[1], (B, 2, T)),
                 jax.random.normal(ks[2], (B, S, 4)))
    weight = jnp.where(visible, jnp.cos(jnp.arange(S))[None, None], 0.0)
    for x, y in zip(
            jax.grad(lambda *a: jnp.sum(weight * sa.indexer_scores(
                *a, OFFSET, BQ, BK)), argnums=(0, 1, 2))(qi, w, ki),
            jax.grad(lambda *a: jnp.sum(weight * sa.indexer_reference(*a)),
                     argnums=(0, 1, 2))(qi, w, ki)):
        _close(x, y)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel"])
def test_the_threshold_is_the_kth_value_and_ties_go_to_the_earlier_key(
        interpret):
    """Three of a row that holds 3, then 2 three times: the threshold is 2,
    four values reach it, and the cut after the first two 2s drops the
    third, as ``lax.top_k`` keeps the earlier of equal values; a row
    without ties keeps every key at its value. The kernel's bisection over
    the keys' indices gives the plain form's cut."""
    rows = np.full((8, 128), -np.inf, np.float32)
    rows[0, :6] = [3.0, 1.0, 2.0, 2.0, 2.0, 0.5]
    rows[1, :4] = [0.5, 4.0, 1.0, 2.0]
    rows[2:] = np.random.default_rng(0).normal(size=(6, 128))
    rows[2, 100] = rows[2, 7] = rows[2, 40]      # a tie across the row
    x = jnp.asarray(rows)[None]
    want = jnp.asarray([[3, 2, 64, 5, 6, 7, 8, 9]])
    value, cut = (sa.kth_largest_pallas(x, want, rows=8, interpret=True)
                  if interpret else sa.kth_largest(x, want, 64))
    assert float(value[0, 0]) == 2.0 and int(cut[0, 0]) == 3
    for r in range(8):
        chosen = np.flatnonzero((x[0, r] > value[0, r])
                                | ((x[0, r] == value[0, r])
                                   & (np.arange(128) <= cut[0, r])))
        assert chosen.tolist() == sorted(np.asarray(jax.lax.top_k(
            x[0, r], int(want[0, r]))[1]).tolist()), r


# -- configuration and the state row ----------------------------------------


def test_configuration_takes_the_kind_and_refuses_what_it_cannot_run():
    core = tiny_config().network.core
    assert core.kind == KIND and core.scoring_func == "softmax"
    # its own keys have no sizes of their own: a configuration spells each
    for bad in ({"scoring_func": "relu"}, {"num_key_value_heads": 3},
                {"head_dim": 7}, {"index_topk": 0}, {"head_dim": 0},
                {"index_head_dim": 0}, {"index_n_heads": 0},
                {"q_chunk_size": 0}, {"kv_chunk_size": 0}):
        with pytest.raises(ValueError):
            CoreConfig(**{**TINY_SPARSE_CORE, **bad})
    # the other cores keep the sigmoid form unless told
    assert CoreConfig(kind="mla_moe").scoring_func == "sigmoid"


def test_state_row_and_record_block():
    cfg = tiny_config()
    # 2 layers x 4 positions x (2 x 2 heads x 8 + 8 indexer) = 320 floats
    assert state_half(cfg.network) == 160
    block = state_block(cfg.network)
    assert block["parts"] == [
        {"kind": "key_value_window", "layers": 2, "floats": 256,
         "bytes": 1024},
        {"kind": "indexer_keys", "layers": 2, "floats": 64, "bytes": 256}]
    assert block["dsa"] == {"index_topk": 8, "index_n_heads": 2,
                            "index_head_dim": 8, "q_chunk_size": 8,
                            "kv_chunk_size": 8}
    # the cell's row: 4 x 128 x (4 x 128 x 2 + 64) floats = 2.125 MiB
    full = CoreConfig(kind=KIND, num_hidden_layers=4, num_attention_heads=32,
                      num_key_value_heads=4, head_dim=128, memory_len=128,
                      n_routed_experts=128, num_experts_per_tok=8,
                      experts_held=16, index_n_heads=16, index_head_dim=64,
                      index_topk=2048, q_chunk_size=512, kv_chunk_size=512)
    assert 8 * make_core(dataclasses.replace(
        Config().network, core=full), jnp.float32).state_half \
        == 2.125 * 2**20


def test_the_records_dsa_block_is_the_cores_summary_of_its_counters():
    """What the learner's aggregator sums of the core's per-layer counters,
    over a dispatch of one step (L,) and one of two (K, L), the core makes
    into the record's ``dsa`` block; a flush with nothing new is None."""
    from r2d2_tpu.telemetry.learning import CoreAggregator
    core = make_core(tiny_config().network, jnp.float32)
    agg = CoreAggregator(core.summarise)
    one = {"queries": [60, 60], "selecting": [30, 0], "pairs": [400, 300],
           "far": [10, 0], "blocks_skipped": [1, 0], "loss": [0.5, 0.25]}
    agg.on_dispatch({f"core/{k}": jnp.asarray(v) for k, v in one.items()}
                    | {"loss_td": jnp.asarray(1.0)})
    agg.on_dispatch({f"core/{k}": jnp.asarray([v, v]) for k, v in one.items()})
    block = agg.flush()
    assert block == {"dsa": {"steps": 3, "pairs": 2100, "layers": [
        {"selecting_share": 0.5, "keys_per_query": 400 / 60,
         "far_share": 10 / 400, "blocks_skipped": 3, "indexer_loss": 0.5},
        {"selecting_share": 0.0, "keys_per_query": 5.0, "far_share": 0.0,
         "blocks_skipped": 0, "indexer_loss": 0.25}]}}
    assert agg.flush() is None
    assert not hasattr(make_core(dataclasses.replace(
        tiny_config().network, core=CoreConfig(kind="mla_moe")),
        jnp.float32), "summarise")


# -- the actor's step against the window --------------------------------------


def test_the_actor_step_reproduces_the_window_through_the_stored_prefix():
    """From an episode's start (an empty row) the actor's T = 1 steps, each
    reading the stored prefix the step before left, give the window's Q at
    every position and its final row, while no query chooses: T + memory
    <= topk. (Past that, and past memory_len + 1 steps, the learner's
    window sees further back than the actor did: PERF.md, section 7.)"""
    cfg = tiny_config(index_topk=64)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, _ = inputs(jax.random.PRNGKey(1), 2, 5, net)
    empty = net.init_state(2)
    q_window, row_window = net.apply(params, obs, action, empty)
    state, qs = empty, []
    for t in range(5):
        q, state = net.apply(params, obs[:, t:t + 1], action[:, t:t + 1],
                             state)
        qs.append(q)
    np.testing.assert_allclose(jnp.concatenate(qs, axis=1), q_window,
                               atol=2e-5)
    np.testing.assert_allclose(state, row_window, atol=2e-5)
    # the row carries the last memory_len positions, three kinds of part
    assert np.abs(np.asarray(row_window)).max() > 0


# -- the indexer's loss -------------------------------------------------------


def test_the_indexer_learns_from_its_loss_alone():
    """The window's Q does not depend on the indexer's weights (the choice
    reads no gradient), and the indexer's loss reaches the indexer's
    weights and nothing else (the indexer reads the stream stopped)."""
    cfg = tiny_config()
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(1), 2, 37, net)

    def parts(p):
        q, _, counters = net.apply_learner(p, obs, action, state)
        return jnp.sum(q ** 2), jnp.sum(counters["core"]["loss"])

    from_q = jax.grad(lambda p: parts(p)[0])(params)
    from_kl = jax.grad(lambda p: parts(p)[1])(params)
    assert float(parts(params)[1]) > 0
    seen = {"indexer": 0, "other": 0}
    for (path, gq), gk in zip(
            jax.tree_util.tree_leaves_with_path(from_q),
            jax.tree_util.tree_leaves(from_kl)):
        name = jax.tree_util.keystr(path)
        if "'indexer'" in name:
            assert float(jnp.abs(gq).max()) == 0.0, name
            seen["indexer"] += float(jnp.abs(gk).max()) > 0
        else:
            assert float(jnp.abs(gk).max()) == 0.0, name
            seen["other"] += float(jnp.abs(gq).max()) > 0
    # every indexer leaf of both layers learns from the loss
    assert seen["indexer"] == 2 * 5 and seen["other"] > 20


# -- the router's softmax form, and the sigmoid cores --------------------------


def test_the_softmax_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Eight chips, 2 of 16 routed experts each, top 4 by softmax: the
    shares' parts are the layer that holds all 16, which is the reference's
    uncut layer (no shared expert to count once)."""
    from benchmarks.reference import r2d2_keye
    from r2d2_tpu.models.cores.experts import rms_norm
    from r2d2_tpu.models.cores.sparse_attn_moe import MoE
    whole = dataclasses.replace(tiny_config().network.core,
                                n_routed_experts=16, num_experts_per_tok=4,
                                experts_held=16)
    ones = jnp.ones((whole.hidden_size,))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, whole.hidden_size))
    params = MoE(whole, jnp.float32, True).init(jax.random.PRNGKey(1), x,
                                                ones)["params"]
    assert "e_score_correction_bias" not in params

    def layer(core, p):
        out, stats = MoE(core, jnp.float32, True).apply({"params": p}, x,
                                                        ones)
        return out.reshape(-1, core.hidden_size), stats

    flat = rms_norm(x, ones, whole.rms_norm_eps).reshape(-1, 32)
    uncut, stats = layer(whole, params)
    np.testing.assert_allclose(uncut, r2d2_keye._experts(
        flat, params, dataclasses.asdict(whole)), atol=2e-6)
    total = jnp.zeros_like(uncut)
    for chip in range(8):
        share = dataclasses.replace(whole, experts_held=2,
                                    expert_offset=2 * chip)
        mine = {**params, "experts": {
            k: v[2 * chip:2 * chip + 2]
            for k, v in params["experts"].items()}}
        out, counted = layer(share, mine)
        assert int(counted["dropped"]) == 0
        total = total + out
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert int(stats["chosen"].sum()) == flat.shape[0] * 4


# sha256 of the StableHLO text of lfm2-core's tiny twin's fused learner step
# with its layers 1 and 2 routed (first_k_dense_replace 1), lowered on the
# CPU as the program lowered it before the router's softmax form was added
# (CPU, count): the sigmoid form's program is unchanged. moonlight-core's is
# ``MOONLIGHT_TINY_STEP`` in tests/conftest.py.
LFM2_TINY_STEP = \
    "fea77377b1c22284645c2511d9e5484b82e9aacd2d23dc7e76e9779c56a7c6bb"


@pytest.mark.parametrize("config_name", ["moonlight-core", "lfm2-core"])
def test_the_sigmoid_cores_steps_are_the_parents(config_name):
    from benchmarks import harness
    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_multi_learner_step)
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.replay.device_replay import replay_init
    from r2d2_tpu.replay.structs import ReplaySpec
    from r2d2_tpu.telemetry.learning import LearningDiag
    from r2d2_tpu.telemetry.replaydiag import ReplayDiag
    from tests.conftest import MOONLIGHT_TINY_STEP

    bench = harness.load_benchmark()
    extra = ({"network.core.first_k_dense_replace": 1}
             if config_name == "lfm2-core" else {})
    cfg = Config().replace(**{**harness.program_overrides(
        harness.config_doc(bench, config_name),
        harness.traffic_doc("learner-long"), rehearse=True), **extra})
    spec = ReplaySpec.from_config(cfg)
    net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    train = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), net, cfg.optim))
    ring = jax.eval_shape(lambda: replay_init(spec))
    step = make_multi_learner_step(
        net, spec, cfg.optim, cfg.network.use_double,
        cfg.runtime.resolved_steps_per_dispatch(),
        diag=LearningDiag.from_config(cfg), rdiag=ReplayDiag.from_config(cfg))
    text = step.lower(train, ring).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == {
        "moonlight-core": MOONLIGHT_TINY_STEP,
        "lfm2-core": LFM2_TINY_STEP}[config_name]


# -- the normal path ------------------------------------------------------------


def _argv(overrides):
    def spelt(v):
        if isinstance(v, (list, tuple)):
            return ";".join(",".join(str(x) for x in row) for row in v) \
                if v and isinstance(v[0], (list, tuple)) else \
                ",".join(str(x) for x in v)
        return str(v)
    return [f"--{k}={spelt(v)}" for k, v in overrides.items()]


def test_cli_train_trains_and_acts_with_the_benchmarks_overrides(tmp_path):
    """``cli.train`` with ``benchmarks/configs/keye-core.json``'s overrides
    at the rehearsal size with its layers routed: thread actors act through
    the plain forward (T = 1, their rows go to the ring), the fused learner
    step trains on the TD loss and the indexer's, the record carries the
    ``moe``, ``dsa`` and ``core`` blocks."""
    from r2d2_tpu.cli import train as cli
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye-core.json")) as f:
        doc = json.load(f)
    overrides = {**doc["overrides"], **doc["rehearsal"],
                 # the rehearsal's twin is all dense (``rehearsal_note``)
                 "network.core.first_k_dense_replace": 0,
                 "env.game_name": "Fake", "network.bf16": "off",
                 "replay.learning_starts": 64, "actor.num_actors": 2,
                 "runtime.save_dir": str(tmp_path),
                 "runtime.save_interval": 0, "runtime.log_interval": 0.2,
                 "runtime.steps_per_dispatch": 2}
    stacks = cli.main(_argv(overrides) + ["--actor-mode=thread",
                                          "--max-steps=6",
                                          "--max-seconds=300"])
    learner = stacks[0].learner
    assert learner.cfg.network.core.kind == KIND
    assert int(learner.train_state.step) >= 6
    assert learner.env_steps >= 64
    # the actors' rows are the core's: every kind of part was written
    stored = np.asarray(learner.replay_state.hidden)
    flat = stored.reshape(-1, 2 * learner.net.state_half)
    assert np.abs(flat).max() > 0
    records = [json.loads(line) for line in
               open(tmp_path / "metrics_player0.jsonl")]
    assert any("moe" in r for r in records)
    dsa = [r["dsa"] for r in records if "dsa" in r]
    assert dsa and dsa[0]["pairs"] > 0
    layer = dsa[0]["layers"][0]
    # a window of 25 over 4 stored positions: queries from step 4 choose 8
    assert 0 < layer["selecting_share"] <= 1
    assert 0 < layer["keys_per_query"] <= 8
    assert np.isfinite(layer["indexer_loss"]) and layer["indexer_loss"] >= 0
    (core_block,) = [r["core"] for r in records if "core" in r]
    assert [p["kind"] for p in core_block["parts"]] == [
        "key_value_window", "indexer_keys"]
    assert core_block["dsa"]["index_topk"] == 8
