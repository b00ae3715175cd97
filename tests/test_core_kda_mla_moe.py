"""The ``kda_mla_moe`` core's own cases (``r2d2_tpu/models/cores/
kda_mla_moe.py``) and its scan's kernels (``r2d2_tpu/ops/kda.py``): the
kernels against the per-position recurrence in interpret mode, forward and
backward, where the window is not whole chunks, one position, and the gates
at the ends of their range; the core through the kernels against the core
through the recurrence; the configuration keys and the state row; the
actor's step against the window; the sigmoid experts' shares against the
uncut layer; the latent attention's blocks of queries and its NoPE form,
with ``moonlight-core``'s program left as it was; the core on the normal
path (``cli.train``). The comparison with the plain reference is in
``tests/benchmarks/test_bm_kimi.py``. Tiny sizes, CPU, seeded weights."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import Config, CoreConfig
from r2d2_tpu.models.cores import make_core, state_block, state_half
from r2d2_tpu.ops import kda
from tests.test_cores import TINY_ENV, inputs, tiny_net

KIND = "kda_mla_moe"
TINY_KDA_CORE = {
    "kind": KIND, "hidden_size": 32, "num_hidden_layers": 3,
    "layer_types": ("kda", "full_attention", "kda"),
    "num_attention_heads": 2, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "intermediate_size": 64,
    "moe_intermediate_size": 16, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "experts_held": 4, "memory_len": 4, "kda_num_heads": 2,
    "kda_head_dim": 8, "short_conv_kernel_size": 4, "kda_chunk_size": 8,
    "mla_use_nope": True, "q_chunk_size": 4, "rms_norm_eps": 1e-5,
    "routed_scaling_factor": 2.446}


def tiny_config(**core) -> Config:
    sizes = {f"network.core.{k}": v
             for k, v in {**TINY_KDA_CORE, **core}.items()}
    return Config().replace(**{**TINY_ENV, **sizes})


def _close(got, want, atol=2e-6):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=atol * max(np.abs(want).max(), 1.0))


# -- the kernels against the recurrence, in interpret mode ------------------

CHUNK = 16
CASES = {
    "ragged_window": dict(t=20),
    "one_position": dict(t=1),
    "whole_chunks": dict(t=32),
    # exp(g) near 0: every channel forgets within a few positions
    "decay_near_zero": dict(t=24, rate=30.0),
    # exp(g) near 1 and beta near 1: the delta rule at full strength with
    # nothing forgotten
    "decay_near_one_beta_near_one": dict(t=24, rate=1e-4, beta=(0.99, 1.0)),
    "beta_near_zero": dict(t=24, beta=(0.0, 0.01)),
}


def _scan_case(t, rate=1.0, beta=(0.0, 1.0), b=2, h=2, d=16, seed=0):
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    arrays = (unit(b, h, t, d), unit(b, h, t, d),
              rng.standard_normal((b, h, t, d)),
              -rate * np.abs(rng.standard_normal((b, h, t, d))),
              rng.uniform(*beta, size=(b, h, t)),
              0.3 * rng.standard_normal((b, h, d, d)))
    return [jnp.asarray(x, jnp.float32) for x in arrays]


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("part", ["forward", "backward"])
@pytest.mark.parametrize("block", [8, kda.BLOCK])
def test_kernels_match_the_recurrence_in_interpret_mode(case, part, block,
                                                        monkeypatch):
    """Rows in blocks of 8 (a chunk's pairs with earlier blocks as matrix
    products) and of the module's own (a chunk of 16 as one block)."""
    monkeypatch.setattr(kda, "BLOCK", block)
    q, k, v, g, beta, s0 = _scan_case(**CASES[case])
    o, s, states = kda.chunked_forward(q, k, v, g, beta, s0, CHUNK,
                                       interpret=True)
    o_want, s_want = kda.recurrent(q, k, v, g, beta, s0)
    if part == "forward":
        _close(o, o_want)
        _close(s, s_want)
        # each chunk starts from the state the recurrence holds there
        _close(states[:, :, 0], s0)
        return
    rng = np.random.default_rng(5)
    do = jnp.asarray(rng.standard_normal(o_want.shape), jnp.float32)
    ds = jnp.asarray(rng.standard_normal(s_want.shape), jnp.float32)
    want = jax.vjp(kda.recurrent, q, k, v, g, beta, s0)[1]((do, ds))
    got = kda.chunked_backward(q, k, v, g, beta, states, do, ds, CHUNK,
                               interpret=True)
    for x, y in zip(got, want):
        _close(x, y, 1e-5)


def test_the_scan_takes_the_recurrence_where_the_kernels_do_not_tile():
    """Off the TPU, or at widths that are not whole lanes, ``kda_scan`` is
    the recurrence, its gradient the recurrence's."""
    q, k, v, g, beta, s0 = _scan_case(t=20)
    assert not kda.tiles(16, 16, CHUNK) and kda.tiles(128, 128, 64)

    def loss(fn, *x):
        o, s = fn(*x)
        return jnp.sum(jnp.sin(o)) + jnp.sum(s * s)

    args = (q, k, v, g, beta, s0)
    _close(loss(lambda *x: kda.kda_scan(*x, CHUNK), *args),
           loss(kda.recurrent, *args))
    got = jax.grad(lambda *x: loss(lambda *y: kda.kda_scan(*y, CHUNK), *x),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(lambda *x: loss(kda.recurrent, *x),
                    argnums=tuple(range(6)))(*args)
    for x, y in zip(got, want):
        _close(x, y)


def _interpreted_scan():
    """``kda_scan`` through the kernels in interpret mode, with their
    gradient: what the chip runs, on the CPU."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
    def scan(q, k, v, g, beta, s0, chunk):
        o, s, _ = kda.chunked_forward(q, k, v, g, beta, s0, chunk, True)
        return o, s

    def fwd(q, k, v, g, beta, s0, chunk):
        o, s, states = kda.chunked_forward(q, k, v, g, beta, s0, chunk, True)
        return (o, s), (q, k, v, g, beta, states)

    def bwd(chunk, res, cot):
        q, k, v, g, beta, states = res
        return kda.chunked_backward(q, k, v, g, beta, states, *cot, chunk,
                                    True)

    scan.defvjp(fwd, bwd)
    return scan


def test_the_core_through_the_kernels_is_the_core_through_the_recurrence(
        monkeypatch):
    """A window of 20 positions (three chunks of 8, the last ragged) from a
    random stored row: Q, the new row and the gradient of both, the scan
    by the kernels against the scan by the recurrence."""
    cfg = tiny_config()
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, _ = inputs(jax.random.PRNGKey(1), 2, 20, net)
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                    net.init_state(2).shape)

    def loss(p):
        q, row = net.apply(p, obs, action, state)
        return jnp.sum(jnp.sin(q)) + jnp.sum(jnp.cos(row)), (q, row)

    (_, want), grad_want = jax.value_and_grad(loss, has_aux=True)(params)
    monkeypatch.setattr(kda, "kda_scan", _interpreted_scan())
    (_, got), grad_got = jax.value_and_grad(loss, has_aux=True)(params)
    for x, y in zip(jax.tree_util.tree_leaves((got, grad_got)),
                    jax.tree_util.tree_leaves((want, grad_want))):
        _close(x, y, 2e-5)
    grads = jax.tree_util.tree_leaves_with_path(grad_got)
    reached = {jax.tree_util.keystr(p) for p, x in grads
               if float(jnp.abs(x).max()) > 0}
    for name in ("'q_proj'", "'k_conv1d'", "'A_log'", "'dt_bias'",
                 "'b_proj'", "'g_b_bias'", "'kv_b_proj'", "'experts'"):
        assert any(name in r for r in reached), name


# -- the configuration and the state row --------------------------------------


def test_configuration_takes_the_kind_and_refuses_what_it_cannot_run():
    core = tiny_config().network.core
    assert core.kind == KIND and core.layer_types[1] == "full_attention"
    for bad in ({"layer_types": ("kda", "conv", "kda")},
                {"layer_types": ("kda", "kda")},
                {"kda_num_heads": 0}, {"kda_chunk_size": 0},
                {"short_conv_kernel_size": 1}, {"q_chunk_size": -1}):
        with pytest.raises(ValueError, match="network.core"):
            dataclasses.replace(core, **bad)
    # the other cores are refused the new kind's layer names, and read none
    # of its keys
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(core, kind="conv_attn_moe")
    assert CoreConfig().kda_chunk_size == 0 and not CoreConfig().mla_use_nope


def test_state_row_and_record_block():
    """Per KDA layer 2 x 8 x 8 state + 3 positions of 3 x 16 inputs, per
    latent layer 4 x 12 latents: the row, its parts, the one-shot block."""
    cfg = tiny_config()
    core = make_core(cfg.network, jnp.float32)
    kda_layer = 2 * 8 * 8 + 3 * 3 * 16
    assert 2 * state_half(cfg.network) == 2 * kda_layer + 4 * 12
    assert core.state_parts() == [("kda_state", 2, 2 * 128),
                                  ("conv_tail", 2, 2 * 144),
                                  ("latent_cache", 1, 48)]
    block = state_block(cfg.network, 2, 20)
    assert block["row_bytes"] == 8 * core.state_half
    assert [p["kind"] for p in block["parts"]] == [
        "kda_state", "conv_tail", "latent_cache"]
    assert block["kda"] == {"layers": 2, "heads": 2, "head_dim": 8,
                            "chunk": 8, "chunks_per_window": 3,
                            "layer_row_bytes": 4 * kda_layer}
    # the benchmark's configuration: 8.84 MiB a sequence
    bench = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "kimi-linear-core.json")))
    full = Config().replace(**{k: tuple(v) if isinstance(v, list) else v
                               for k, v in bench["overrides"].items()
                               if k.startswith("network.core.")})
    assert state_half(full.network) == 1_159_168


def test_the_records_kda_block_is_the_cores_summary_of_its_counters():
    """The learner's call sows per KDA layer its decays, beta and the
    state's norm; the train step takes the per-position ones over the
    learning positions; the record's block is their mean over steps."""
    from r2d2_tpu.telemetry.learning import CoreAggregator
    cfg = tiny_config().replace(**{
        "sequence.burn_in_steps": 4, "sequence.learning_steps": 12,
        "sequence.forward_steps": 2, "replay.block_length": 12,
        "replay.capacity": 96, "replay.batch_size": 2})
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, _ = inputs(jax.random.PRNGKey(1), 2, 18, net)
    _, _, counters = net.apply_learner(params, obs, action,
                                       net.init_state(2))
    own = counters["core"]
    assert own["log_decay"].shape == (2, 2, 18)
    assert own["state_norm"].shape == (2,)
    assert float(own["log_decay"].max()) < 0
    assert 0 < float(own["beta"].min()) <= float(own["beta"].max()) < 1
    assert 0 <= float(own["forgets_in_chunk"].min())
    agg = CoreAggregator(net.core.summarise)
    sums = {k: v.reshape(2, -1).mean(axis=-1) if v.ndim == 3 else v
            for k, v in own.items()}
    agg.on_dispatch({f"core/{k}": v for k, v in sums.items()})
    agg.on_dispatch({f"core/{k}": v for k, v in sums.items()})
    block = agg.flush()["kda"]
    assert block["steps"] == 2 and len(block["layers"]) == 2
    assert block["layers"][0]["beta"] == pytest.approx(
        float(sums["beta"][0]), rel=1e-6)


def test_the_actor_step_reproduces_the_window_through_the_stored_row():
    """From an episode's start (an empty row) the actor's T = 1 steps, each
    reading the row the step before left, give the window's Q at every
    position and its final row: KDA's state and the convolutions' inputs
    carry everything, the latent cache its memory_len + 1 positions."""
    cfg = tiny_config()
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
    assert np.abs(np.asarray(row_window)).max() > 0
    # with no latent layer the actor follows the window at any length
    only = tiny_config(layer_types=("kda", "kda", "kda"))
    net = tiny_net(only)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, _ = inputs(jax.random.PRNGKey(1), 2, 12, net)
    q_window, row_window = net.apply(params, obs, action, net.init_state(2))
    state, qs = net.init_state(2), []
    for t in range(12):
        q, state = net.apply(params, obs[:, t:t + 1], action[:, t:t + 1],
                             state)
        qs.append(q)
    np.testing.assert_allclose(jnp.concatenate(qs, axis=1), q_window,
                               atol=2e-5)
    np.testing.assert_allclose(state, row_window, atol=2e-5)


# -- the experts' shares ------------------------------------------------------


def test_the_sigmoid_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips, 2 of 8 routed experts each, top 2 by sigmoid, one shared
    expert: the chips' outputs less the shared expert's, summed, and the
    shared expert once, are the layer that holds all 8, which is the
    reference's uncut layer."""
    from benchmarks.reference import r2d2_mla_moe
    from r2d2_tpu.models.cores.experts import rms_norm
    from r2d2_tpu.models.cores.mla_moe import MoE
    whole = dataclasses.replace(tiny_config().network.core, experts_held=8)
    ones = jnp.ones((whole.hidden_size,))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, whole.hidden_size))
    params = MoE(whole, jnp.float32, True).init(jax.random.PRNGKey(1), x,
                                                ones)["params"]

    def layer(core, p):
        out, stats = MoE(core, jnp.float32, True).apply({"params": p}, x,
                                                        ones)
        return out.reshape(-1, core.hidden_size), stats

    flat = rms_norm(x, ones, whole.rms_norm_eps).reshape(-1, 32)
    uncut, stats = layer(whole, params)
    np.testing.assert_allclose(uncut, r2d2_mla_moe._experts(
        flat, params, dataclasses.asdict(whole)), atol=2e-6)
    shared = r2d2_mla_moe._swiglu(flat, params["shared_experts"])
    total = shared
    for chip in range(4):
        share = dataclasses.replace(whole, experts_held=2,
                                    expert_offset=2 * chip)
        mine = {**params, "experts": {
            k: v[2 * chip:2 * chip + 2]
            for k, v in params["experts"].items()}}
        out, counted = layer(share, mine)
        assert int(counted["dropped"]) == 0
        total = total + out - shared
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert int(stats["chosen"].sum()) == flat.shape[0] * 2


# -- the latent attention: NoPE and blocks of queries -------------------------


def _latent(core, t=12, seed=0):
    from r2d2_tpu.models.cores.mla_moe import LatentAttention
    width = core.kv_lora_rank + core.qk_rope_head_dim
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k[0], (2, t, core.hidden_size))
    # a stored cache with its two oldest slots empty
    mem = jax.random.normal(k[1], (2, core.memory_len, width))
    mem = mem.at[:, :2].set(0.0)
    ones = jnp.ones((core.hidden_size,))
    module = LatentAttention(core, jnp.float32)
    params = module.init(k[2], x, ones, mem)
    return module, params, x, ones, mem


def test_blocked_queries_are_the_whole_windows_attention():
    """Blocks of 4 queries over a window of 12 give the output, the rows
    and the gradients of the window at once, NoPE on both sides."""
    core = tiny_config().network.core
    module, params, x, ones, mem = _latent(core)

    def run(c, p, x):
        out, rows = module.clone(core=c).apply(p, x, ones, mem)
        return jnp.sum(jnp.sin(out)) + jnp.sum(rows), (out, rows)

    whole = dataclasses.replace(core, q_chunk_size=0)
    (_, want), g_want = jax.value_and_grad(run, argnums=(1, 2),
                                           has_aux=True)(whole, params, x)
    (_, got), g_got = jax.value_and_grad(run, argnums=(1, 2),
                                         has_aux=True)(core, params, x)
    for a, b in zip(jax.tree_util.tree_leaves((got, g_got)),
                    jax.tree_util.tree_leaves((want, g_want))):
        _close(a, b, 1e-5)
    # a block that does not divide the window, or covers it: the whole
    for block in (5, 12, 16):
        other = dataclasses.replace(core, q_chunk_size=block)
        out, _ = module.clone(core=other).apply(params, x, ones, mem)
        _close(out, want[0], 1e-5)


def test_nope_is_the_references_latent_attention_and_rope_is_not():
    """The NoPE form, in blocks of queries, is the plain reference's latent
    attention (no rotation of the 64-wide shared part, blocks or not); the
    RoPE form of the same weights is not."""
    from benchmarks.reference import r2d2_kimi
    from r2d2_tpu.models.cores.experts import rms_norm
    core = tiny_config().network.core
    module, params, x, ones, mem = _latent(core)
    got, rows = module.apply(params, x, ones, mem)
    want, own = r2d2_kimi._latent_attention(
        rms_norm(x, ones, core.rms_norm_eps), mem, params["params"],
        dataclasses.asdict(core))
    _close(got, want, 1e-5)
    _close(rows, own, 1e-5)
    rope = dataclasses.replace(core, mla_use_nope=False, q_chunk_size=0)
    rotated, _ = module.clone(core=rope).apply(params, x, ones, mem)
    assert float(jnp.abs(rotated - got).max()) > 1e-2 * float(
        jnp.abs(got).max())


def test_moonlights_step_and_cost_table_are_the_parents():
    """The latent attention's new forms are off in ``moonlight-core``
    (RoPE, no blocks): its tiny twin's step lowers to the recorded text,
    and BASELINE.json's exact cost table of the LSTM step is unmoved."""
    import hashlib

    from benchmarks import harness
    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_multi_learner_step)
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.replay.device_replay import replay_init
    from r2d2_tpu.replay.structs import ReplaySpec
    from r2d2_tpu.telemetry import costmodel
    from r2d2_tpu.telemetry.learning import LearningDiag
    from r2d2_tpu.telemetry.replaydiag import ReplayDiag
    from tests.conftest import MOONLIGHT_TINY_STEP

    bench = harness.load_benchmark()
    cfg = Config().replace(**harness.program_overrides(
        harness.config_doc(bench, "moonlight-core"),
        harness.traffic_doc("learner-long"), rehearse=True))
    assert not cfg.network.core.mla_use_nope
    assert cfg.network.core.q_chunk_size == 0
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
    assert hashlib.sha256(text.encode()).hexdigest() == MOONLIGHT_TINY_STEP
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BASELINE.json")) as f:
        want = json.load(f)["costs"]["programs"]["learner_step"]
    got = costmodel.collect_cost_table(
        costmodel.gate_config(), variants=("learner_step",)
    )["programs"]["learner_step"]
    assert (got["flops"], got["bytes_accessed"]) == (
        want["flops"], want["bytes_accessed"])


# -- the normal path ----------------------------------------------------------


def _argv(overrides):
    def spelt(v):
        if isinstance(v, (list, tuple)):
            return ";".join(",".join(str(x) for x in row) for row in v) \
                if v and isinstance(v[0], (list, tuple)) else \
                ",".join(str(x) for x in v)
        return str(v)
    return [f"--{k}={spelt(v)}" for k, v in overrides.items()]


def test_cli_train_trains_and_acts_with_the_benchmarks_overrides(tmp_path):
    """``cli.train`` with ``benchmarks/configs/kimi-linear-core.json``'s
    overrides at the rehearsal size with its layers 2 and 3 routed: thread
    actors act through the plain forward (T = 1, their rows go to the
    ring), the fused learner step trains, the record carries the ``moe``,
    ``kda`` and ``core`` blocks."""
    from r2d2_tpu.cli import train as cli
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi-linear-core.json")) as f:
        doc = json.load(f)
    overrides = {**doc["overrides"], **doc["rehearsal"],
                 # the rehearsal's twin is all dense (``rehearsal_note``)
                 "network.core.first_k_dense_replace": 1,
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
    stored = np.asarray(learner.replay_state.hidden)
    flat = stored.reshape(-1, 2 * learner.net.state_half)
    assert np.abs(flat).max() > 0
    records = [json.loads(line) for line in
               open(tmp_path / "metrics_player0.jsonl")]
    assert any("moe" in r for r in records)
    blocks = [r["kda"] for r in records if "kda" in r]
    assert blocks and blocks[0]["steps"] > 0
    layer = blocks[0]["layers"][0]
    assert layer["log_decay"] < 0 and 0 < layer["beta"] < 1
    assert 0 <= layer["forgets_in_chunk"] <= 1 and layer["state_norm"] > 0
    (core_block,) = [r["core"] for r in records if "core" in r]
    assert [p["kind"] for p in core_block["parts"]] == [
        "kda_state", "conv_tail", "latent_cache"]
    assert core_block["kda"]["chunk"] == 8
