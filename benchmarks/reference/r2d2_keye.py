"""Plain float32 reference of the R2D2 loss with the ``sparse_attn_moe``
memory core: the torso, the dueling head and the loss of
``reference/r2d2.py`` (as ``reference/r2d2_mla_moe.py`` spells them around a
core, imported), and between them a stack of Keye-VL-2.0-30B-A3B's language
model layers written from the source's ``config.json``
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``model_type: KeyeVL2``, its ``sa_config``) in straightforward ``jax.numpy``:
a Python loop over layers, dense ``einsum``s, each query's chosen keys found
by ``jax.lax.top_k`` over the indexer's scores and gathered by their
indices (no mask, no threshold, no kernel), a loop over the experts held
with a mask. No bf16, and none of the program's functions: the state row's
layout is written out here again.

Per layer on the residual stream x (d wide), pre-norm RMSNorm, no biases,
and a final RMSNorm before the head:

  * x += Attn(RMSNorm(x)): ``q = W_q h`` in H heads of e = ``head_dim``,
    ``k = W_k h`` and ``v = W_v h`` in G heads; q and k through an RMSNorm
    over e with a learned weight each; the keys, values and indexer keys are
    the sequence's stored slots (``memory_len`` positions of (k after its
    norm, before its rotation | v | kI after its norm, before its
    rotation), oldest first) followed by the window's own; the indexer reads
    h with no gradient: ``qI = W_qI h`` in J heads of c, ``kI =
    LayerNorm(W_kI h)``, ``w = W_w h / sqrt(J c)``; q, k, qI and kI rotated
    (pairs (i, i + width/2), angle position * theta^(-2i/width)), stored slot
    j at position j - memory_len, window step t at t; a query sees the
    stored slots that are not all zero and the window up to itself;
    ``I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s])``; the chosen keys are
    ``lax.top_k`` of I over the visible keys (``index_topk`` of them, or
    every visible key where a query sees no more; equal scores to the
    earlier key); query head h reads key/value head h // (H / G); softmax of
    q.k / sqrt(e) over the chosen keys; ``W_o``.
  * x += FFN(RMSNorm(x)): the first ``first_k_dense_replace`` layers
    ``W_2(silu(W_1 h) * W_3 h)``; the others ``p = softmax(W_r (h - m))``
    over all routed experts, m the mean of h over all positions of the call
    (a constant to the gradient), the chosen the top-k of p, ``g_i = p_i /
    sum over the chosen of p * routed_scaling_factor``, the sum over the
    experts both chosen and held of ``g_i E_i(h)``. What the absent experts
    would add is left out, as in the program.
  * The indexer's loss of a position: KL(P || softmax of I over the chosen
    keys), P the heads' mean attention over the chosen keys, a constant;
    ``learning_mean`` averages it over a batch's learning positions, per
    layer.

Departures from the source are the program's (``benchmarks/configs/
keye-core.json``, ``assumed``). At the cell's 8,192 positions the attention
is computed in blocks of ``QUERY_BLOCK`` queries, which changes no value.
Weights are the program's parameter tree; this file only reads arrays out
of it.
"""

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import r2d2_mla_moe
from benchmarks.reference.r2d2_lfm2 import _rebind
from benchmarks.reference.r2d2_mla_moe import _norm, _rotate, _swiglu

# queries a block of the attention takes at once where the window is longer
QUERY_BLOCK = 32


def row_width(core: Dict[str, Any]) -> int:
    """Floats of one stored position of one layer: k | v | kI."""
    return (2 * core["num_key_value_heads"] * core["head_dim"]
            + core["index_head_dim"])


def _layer_norm(x, weight, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _attention(h, stored, p, core):
    """h (B, T, d); stored (B, M, row_width) -> ((B, T, d), KL (B, T), the
    chosen keys (B, T, M + T) bool)."""
    heads, groups, e = (core["num_attention_heads"],
                        core["num_key_value_heads"], core["head_dim"])
    j, c, topk = (core["index_n_heads"], core["index_head_dim"],
                  core["index_topk"])
    m, eps, theta = core["memory_len"], core["rms_norm_eps"], core["rope_theta"]
    b, t, d = h.shape
    n = heads // groups
    q = _norm(jnp.einsum("btd,dgne->btgne", h, p["q_proj"]),
              p["q_norm"]["weight"], eps).reshape(b, t, heads, e)
    k = _norm(jnp.einsum("btd,dge->btge", h, p["k_proj"]),
              p["k_norm"]["weight"], eps)
    v = jnp.einsum("btd,dge->btge", h, p["v_proj"])
    ix, still = p["indexer"], jax.lax.stop_gradient(h)
    q_idx = jnp.einsum("btd,dje->btje", still, ix["wq"])
    k_idx = _layer_norm(still @ ix["wk"], ix["k_norm_weight"],
                        ix["k_norm_bias"], eps)
    w_idx = (still @ ix["weights_proj"]) / jnp.sqrt(float(j * c))

    ge = groups * e
    positions = jnp.arange(-m, t)
    keys = _rotate(jnp.concatenate(
        [stored[..., :ge].reshape(b, m, groups, e), k], axis=1), positions,
        theta)
    values = jnp.concatenate(
        [stored[..., ge:2 * ge].reshape(b, m, groups, e), v], axis=1)
    keys_idx = _rotate(jnp.concatenate([stored[..., 2 * ge:], k_idx],
                                       axis=1), positions, theta)
    q = _rotate(q, jnp.arange(t), theta)
    q_idx = _rotate(q_idx, jnp.arange(t), theta)
    filled = jnp.concatenate([jnp.any(stored != 0, axis=-1),
                              jnp.ones((b, t), bool)], axis=1)
    most = min(topk, m + t)

    def block(at):
        """Queries ``at`` (Q,): their output (B, Q, d), KL (B, Q) and chosen
        keys (B, Q, M + T)."""
        visible = filled[:, None, :] & (positions[None, :]
                                        <= at[:, None])[None]
        scores = jnp.einsum("bqj,bqjs->bqs", w_idx[:, at], jax.nn.relu(
            jnp.einsum("bqjc,bsc->bqjs", q_idx[:, at], keys_idx)))
        scores = jnp.where(visible, scores, -jnp.inf)
        top, index = jax.lax.top_k(jax.lax.stop_gradient(scores), most)
        real = top > -jnp.inf                               # (B, Q, K)
        chosen_k = jax.vmap(lambda x, i: x[i])(keys, index)  # (B,Q,K,G,e)
        chosen_v = jax.vmap(lambda x, i: x[i])(values, index)
        qb = q[:, at].reshape(b, len(at), groups, n, e)
        logits = jnp.einsum("bqgne,bqkge->bqgnk", qb, chosen_k) \
            / jnp.sqrt(float(e))
        probs = jax.nn.softmax(
            jnp.where(real[:, :, None, None], logits, -jnp.inf), axis=-1)
        out = jnp.einsum("bqgnk,bqkge->bqgne", probs, chosen_v)
        out = jnp.einsum("bqgne,gned->bqd", out, p["o_proj"])
        heads_mean = jax.lax.stop_gradient(probs.mean(axis=(2, 3)))
        picked = jnp.take_along_axis(scores, index, axis=-1)
        log_q = jax.nn.log_softmax(jnp.where(real, picked, -jnp.inf), axis=-1)
        kl = jnp.sum(jnp.where(
            real, jax.scipy.special.xlogy(heads_mean, heads_mean)
            - heads_mean * jnp.where(real, log_q, 0.0), 0.0), axis=-1)
        chosen = jax.vmap(jax.vmap(lambda i, r: jnp.zeros(
            m + t, bool).at[i].set(r)))(index, real)
        return out, kl, chosen

    if t > QUERY_BLOCK and t % QUERY_BLOCK == 0:
        out, kl, chosen = jax.lax.map(
            block, jnp.arange(t).reshape(-1, QUERY_BLOCK))
        return (out.transpose(1, 0, 2, 3).reshape(b, t, d),
                kl.transpose(1, 0, 2).reshape(b, t),
                chosen.transpose(1, 0, 2, 3).reshape(b, t, m + t))
    return block(jnp.arange(t))


def _experts(h, p, core):
    """h (N, d) -> (N, d): the held share of the routed experts, softmax
    scores."""
    width = core["moe_intermediate_size"]
    centred = h - jax.lax.stop_gradient(h.mean(axis=0))
    s = jax.nn.softmax(centred @ p["gate"], axis=-1)        # (N, routed)
    picked, chosen = jax.lax.top_k(s, core["num_experts_per_tok"])
    g = picked / picked.sum(-1, keepdims=True) \
        * core["routed_scaling_factor"]
    out = jnp.zeros_like(h)
    for local in range(core["experts_held"]):
        expert = core["expert_offset"] + local
        weight = jnp.sum(jnp.where(chosen == expert, g, 0.0), axis=-1)
        gate_up = h @ p["experts"]["gate_up_proj"][local]
        y = (jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]) \
            @ p["experts"]["down_proj"][local]
        out = out + weight[:, None] * y
    return out


def core_forward_kl(p: Dict[str, Any], x_seq, hidden, core: Dict[str, Any]):
    """x_seq (B, T, D_in); hidden (B, 2, half) -> ((B, T, hidden_size), the
    indexer's KL (layers, B, T), the chosen keys (layers, B, T, M + T))."""
    b, t, _ = x_seq.shape
    eps = core["rms_norm_eps"]
    stored = hidden.astype(jnp.float32).reshape(
        b, core["num_hidden_layers"], core["memory_len"], row_width(core))
    x = _norm(x_seq @ p["input_proj"], p["input_norm"]["weight"], eps)
    kls, chosen = [], []
    for i in range(core["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        out, kl, picked = _attention(_norm(x, layer["input_layernorm"], eps),
                                     stored[:, i], layer["self_attn"], core)
        x = x + out
        kls.append(kl)
        chosen.append(picked)
        h = _norm(x, layer["post_attention_layernorm"], eps).reshape(b * t,
                                                                     -1)
        if i < core["first_k_dense_replace"]:
            out = _swiglu(h, layer["mlp"])
        else:
            out = _experts(h, layer["mlp"], core)
        x = x + out.reshape(b, t, -1)
    return (_norm(x, p["norm"]["weight"], eps), jnp.stack(kls),
            jnp.stack(chosen))


def _static(cfg):
    return dict(
        frame_hw=(cfg.env.frame_height, cfg.env.frame_width),
        stack=cfg.env.frame_stack,
        strides=tuple(stride for _, _, stride in cfg.network.conv_layers),
        dueling=cfg.network.use_dueling, double=cfg.network.use_double,
        n_step=cfg.sequence.forward_steps,
        rescale_eps=cfg.optim.value_rescale_eps, eta=cfg.optim.priority_eta)


def learning_mean(kl, batch):
    """Per layer, the mean of a per-position loss ``kl`` (layers, B, T) over
    each window's learning positions (burn-in + j for j < learning steps),
    as the TD loss is taken: the indexer's loss of each layer."""
    burn = jnp.asarray(batch["burn_in_steps"]).astype(jnp.int32)[:, None]
    learn = jnp.asarray(batch["learning_steps"]).astype(jnp.int32)[:, None]
    steps = batch["action"].shape[1]
    at = jnp.arange(steps, dtype=jnp.int32)[None, :]
    valid = (at < learn).astype(jnp.float32)
    picked = jnp.take_along_axis(
        jnp.asarray(kl), jnp.broadcast_to((burn + at)[None], (kl.shape[0],)
                                          + (burn + at).shape), axis=2)
    return jnp.sum(picked * valid[None], axis=(1, 2)) \
        / jnp.maximum(valid.sum(), 1.0)


def from_config(cfg):
    """What every reference module gives the comparison (``check.py``):
    ``fn(params, target_params, batch fields) -> loss_outputs`` at the sizes
    of the program's ``Config``, in true float32 products; beside them, of
    the online window (the first ``loss_outputs`` unrolls), ``indexer_kl``
    (layers, B, T) and ``chosen`` (layers, B, T, M + T), which
    ``runners/learner_8k.py`` holds the program's choice and indexer loss
    to."""
    core = dataclasses.asdict(cfg.network.core)
    static = _static(cfg)

    def run(params, target_params, batch):
        found = []

        def forward(p, x_seq, hidden, core):
            y, kl, chosen = core_forward_kl(p, x_seq, hidden, core)
            found.append((kl, chosen))
            return y

        unroll_q = _rebind(r2d2_mla_moe.unroll_q, dict(
            vars(r2d2_mla_moe), core_forward=forward))
        loss_outputs = _rebind(r2d2_mla_moe.r2d2.loss_outputs, dict(
            vars(r2d2_mla_moe.r2d2),
            unroll_q=functools.partial(unroll_q, core=core)))
        with jax.default_matmul_precision("highest"):
            out = loss_outputs(params, target_params, batch, **static)
        kl, chosen = found[0]
        return {**out, "indexer_kl": kl, "chosen": chosen}
    return jax.jit(run)
