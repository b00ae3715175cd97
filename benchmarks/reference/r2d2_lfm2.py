"""Plain float32 reference of the R2D2 loss with the ``conv_attn_moe`` memory
core: the torso, the dueling head and the loss of ``reference/r2d2.py`` (the
same mathematics; the torso and head as ``reference/r2d2_mla_moe.py`` spells
them around a core, imported), and between them a stack of LFM2-MoE-form
layers written from the source's ``config.json``
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json,
``model_type: lfm2_moe``) in straightforward ``jax.numpy``: a Python loop
over layers, dense ``einsum``s, a loop over the convolution's taps, a loop
over the experts held with a mask (every held expert applied to every
position, weighted by what the positions that chose it gave it). No sort, no
grouped product, no chunk walk, no kernel, no cache object (the stored parts
are concatenated in front of the window's), no bf16, and none of the
program's functions: the layout of the state row is written out here again
(``layout``), and a test holds it to the program's.

Per layer on the residual stream x (d wide), pre-norm, RMSNorm(x) = x /
sqrt(mean x^2 + eps) * weight, no biases, and a final RMSNorm before the head:

  * x += Op(RMSNorm(x)), Op by ``layer_types[i]``:
      - "conv", the gated short convolution: ``[B | C | u] = W_in h`` (d ->
        3d, split in that order); ``z = B * u``; ``c_t = sum_{j < L} w[:, j]
        z_{t-(L-1)+j}`` (depthwise, causal, L = ``conv_L_cache``; the last tap
        multiplies the current position); the positions before the window
        are the sequence's stored part, z at the L - 1 positions before it
        (zeros at an episode's start: the convolution's own left padding);
        ``Op(h) = W_out (C * c)``. No activation.
      - "full_attention", grouped-query attention: ``q = W_q h`` in H heads
        of e = d / H, ``k = W_k h`` and ``v = W_v h`` in G heads; q and k
        through an RMSNorm over e with a learned weight each; the keys and
        values are the sequence's stored part (``memory_len`` positions of
        (k after its norm, before its rotation | v), oldest first) followed
        by the window's own; q and k rotated (pairs (i, i + e/2), angle
        position * theta^(-2i/e)), stored slot j at position j -
        memory_len, window step t at t; query head n reads key/value head
        n // (H / G); scores q.k / sqrt(e); a step sees the stored slots
        that are not all zero and the window up to itself; softmax; ``W_o``.
  * x += FFN(RMSNorm(x)). The first ``first_k_dense_replace`` layers:
    ``W_2(silu(W_1 h) * W_3 h)``. The others: ``s = sigmoid(W_r (h - m))``
    over all routed experts, m the mean of h over all positions of all
    windows of the call (a constant to the gradient); the chosen are the
    top-k of ``s + b``; ``g_i = s_i / (sum over the chosen of s + 1e-6) *
    routed_scaling_factor``; the sum over the experts that are both chosen
    and held (``expert_offset .. expert_offset + experts_held - 1``) of
    ``g_i E_i(h)``. No shared expert. What the absent experts would add is
    left out, as in the program.

Departures from the source, the program's: the input is the torso's output
and the one-hot last action through a bias-free projection and an RMSNorm
where the token embedding stood, the dueling head where the vocabulary
stood; the router reads its input less its mean over the call's positions
(the learner's form; acting, which this file does not compute, subtracts a
stored mean); b enters the choice only; the stored parts are a prefix with
no gradient into them.

Weights are the program's parameter tree, so both sides run the same seeded
weights; this file only reads arrays out of it.
"""

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import r2d2_mla_moe
from benchmarks.reference.r2d2_mla_moe import _norm, _rotate, _swiglu


def layout(core: Dict[str, Any]) -> List[Tuple[str, int, Tuple[int, int]]]:
    """(kind, first float, (positions, width)) of each layer's part of the
    state row, the parts one after the other in layer order: a conv layer
    keeps z at L - 1 positions, an attention layer ``memory_len`` positions
    of (k | v) over its G key/value heads."""
    head = core["hidden_size"] // core["num_attention_heads"]
    parts, at = [], 0
    for kind in core["layer_types"]:
        shape = ((core["conv_L_cache"] - 1, core["hidden_size"])
                 if kind == "conv" else
                 (core["memory_len"], 2 * core["num_key_value_heads"] * head))
        parts.append((kind, at, shape))
        at += shape[0] * shape[1]
    return parts


def _short_conv(h, stored, p, core):
    """h (B, T, d); stored (B, L - 1, d) -> (B, T, d)."""
    taps, t = core["conv_L_cache"], h.shape[1]
    bcu = jnp.einsum("btd,dge->btge", h, p["in_proj"])
    gate_b, gate_c, u = bcu[:, :, 0], bcu[:, :, 1], bcu[:, :, 2]
    z = jnp.concatenate([stored, gate_b * u], axis=1)      # (B, L - 1 + T, d)
    conv = jnp.zeros_like(u)
    for j in range(taps):
        conv = conv + p["conv"][:, j] * z[:, j:j + t]
    return (gate_c * conv) @ p["out_proj"]


def _attention(h, stored, p, core):
    """h (B, T, d); stored (B, M, 2 G e) -> (B, T, d)."""
    d, heads = core["hidden_size"], core["num_attention_heads"]
    groups, m = core["num_key_value_heads"], core["memory_len"]
    e, b, t = d // heads, h.shape[0], h.shape[1]
    eps, theta = core["rms_norm_eps"], core["rope_theta"]
    # the program keeps W_q as (d, G, H / G, e): query head n = g * (H / G) + i
    q = jnp.einsum("btd,dhe->bthe", h, p["q_proj"].reshape(d, heads, e))
    q = _norm(q, p["q_layernorm"]["weight"], eps)
    k = _norm(jnp.einsum("btd,dge->btge", h, p["k_proj"]),
              p["k_layernorm"]["weight"], eps)
    v = jnp.einsum("btd,dge->btge", h, p["v_proj"])
    keys = jnp.concatenate(
        [stored[..., :groups * e].reshape(b, m, groups, e), k], axis=1)
    values = jnp.concatenate(
        [stored[..., groups * e:].reshape(b, m, groups, e), v], axis=1)
    key_positions = jnp.arange(-m, t)
    keys = _rotate(keys, key_positions, theta)
    q = _rotate(q, jnp.arange(t), theta)
    # each query head's own key/value head, written out: H of each
    keys = jnp.repeat(keys, heads // groups, axis=2)
    values = jnp.repeat(values, heads // groups, axis=2)
    scores = jnp.einsum("bthe,bshe->bhts", q, keys) / jnp.sqrt(float(e))
    visible = ((key_positions[None, None, :] <= jnp.arange(t)[None, :, None])
               & jnp.concatenate(
                   [jnp.any(stored != 0, axis=-1),
                    jnp.ones((b, t), bool)], axis=1)[:, None, :])
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhts,bshe->bthe", jax.nn.softmax(scores, axis=-1),
                     values)
    return jnp.einsum("bthe,hed->btd", out, p["out_proj"].reshape(heads, e, d))


def _experts(h, p, core):
    """h (N, d) -> (N, d): the held share of the routed experts."""
    width = core["moe_intermediate_size"]
    centred = h - jax.lax.stop_gradient(h.mean(axis=0))
    s = jax.nn.sigmoid(centred @ p["gate"])                 # (N, routed)
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                              core["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-6) \
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


def core_forward(p: Dict[str, Any], x_seq, hidden, core: Dict[str, Any]):
    """x_seq (B, T, D_in); hidden (B, 2, half) -> (B, T, hidden_size)."""
    b, t, _ = x_seq.shape
    eps = core["rms_norm_eps"]
    row = hidden.astype(jnp.float32).reshape(b, -1)
    x = _norm(x_seq @ p["input_proj"], p["input_norm"]["weight"], eps)
    for i, (kind, at, shape) in enumerate(layout(core)):
        layer = p[f"layers_{i}"]
        stored = row[:, at:at + shape[0] * shape[1]].reshape((b,) + shape)
        h = _norm(x, layer["operator_norm"], eps)
        if kind == "conv":
            x = x + _short_conv(h, stored, layer["conv"], core)
        else:
            x = x + _attention(h, stored, layer["self_attn"], core)
        h = _norm(x, layer["ffn_norm"], eps).reshape(b * t, -1)
        if i < core["first_k_dense_replace"]:
            out = _swiglu(h, layer["mlp"])
        else:
            out = _experts(h, layer["mlp"], core)
        x = x + out.reshape(b, t, -1)
    return _norm(x, p["norm"]["weight"], eps)


def from_config(cfg):
    """What every reference module gives the comparison (``check.py``):
    ``fn(params, target_params, batch fields) -> loss_outputs`` at the sizes
    of the program's ``Config``, in true float32 products: the torso, head
    and loss around ``r2d2_mla_moe``'s core, with this file's core in its
    place."""
    core = dataclasses.asdict(cfg.network.core)
    around = dict(vars(r2d2_mla_moe), core_forward=core_forward)
    unroll_q = _rebind(r2d2_mla_moe.unroll_q, around)
    loss_outputs = _rebind(r2d2_mla_moe.r2d2.loss_outputs, {
        **vars(r2d2_mla_moe.r2d2),
        "unroll_q": functools.partial(unroll_q, core=core)})
    static = dict(
        frame_hw=(cfg.env.frame_height, cfg.env.frame_width),
        stack=cfg.env.frame_stack,
        strides=tuple(stride for _, _, stride in cfg.network.conv_layers),
        dueling=cfg.network.use_dueling, double=cfg.network.use_double,
        n_step=cfg.sequence.forward_steps,
        rescale_eps=cfg.optim.value_rescale_eps, eta=cfg.optim.priority_eta)

    def run(params, target_params, batch):
        with jax.default_matmul_precision("highest"):
            return loss_outputs(params, target_params, batch, **static)
    return jax.jit(run)


def _rebind(fn, names: Dict[str, Any]):
    """``fn``'s code reading its module's names from ``names``: the same
    function around another part (as ``r2d2_mla_moe._loss_outputs`` puts its
    ``unroll_q`` into ``r2d2.loss_outputs``)."""
    import types
    rebound = types.FunctionType(fn.__code__, names, fn.__name__,
                                 fn.__defaults__, fn.__closure__)
    rebound.__kwdefaults__ = fn.__kwdefaults__
    return rebound
