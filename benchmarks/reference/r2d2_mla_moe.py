"""Plain float32 reference of the R2D2 loss with the ``mla_moe`` memory
core: the torso, the dueling head and the loss of ``reference/r2d2.py``
(the same mathematics, imported), and between them a stack of
DeepSeek-V3-form layers written from the source's ``config.json``
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json,
``model_type: deepseek_v3``, ``q_lora_rank: null``) in straightforward
``jax.numpy``: a Python loop over layers, dense ``einsum``s, a loop over the
experts held with a mask. No sort, no grouped product, no cache object (the
stored rows are concatenated in front of the window's), no bf16, and none of
the program's functions.

Per layer on the residual stream x, pre-norm, RMSNorm(x) = x / sqrt(mean
x^2 + eps) * weight, and a final RMSNorm before the head:

  * x += Attn(RMSNorm(x)). ``q = W_q h`` gives each head [q_nope | q_rope];
    ``[c | k_r] = W_kva h``, ``c <- RMSNorm(c)``; ``[k_nope | v]`` per head
    ``= W_kvb c``. The keys are the sequence's stored rows (c after its norm,
    k_r before its rotation; the last ``memory_len`` positions, oldest
    first) followed by the window's own. q_rope and k_r are rotated (pairs
    (i, i + d/2), angle position * theta^(-2i/d)); stored slot j stands at
    position j - memory_len, window step t at t; k_r is shared by the heads.
    Scores (q_nope.k_nope + q_rope.k_r) / sqrt(d_nope + d_rope); a step sees
    the stored slots that are not all zero and the window up to itself;
    softmax; ``W_o`` over the heads' values. No biases.
  * x += FFN(RMSNorm(x)). The first ``first_k_dense_replace`` layers:
    ``W_down(silu(W_gate h) * W_up h)``. The others: ``s = sigmoid(W_r (h -
    m))`` over all routed experts, m the mean of h over all positions of
    all windows of the call (a constant to the gradient); the chosen are
    the top-k of ``s + b``;
    ``g_i = s_i / (sum over the chosen of s + 1e-20) * routed_scaling_factor``;
    the sum over the experts that are both chosen and held (``expert_offset
    .. expert_offset + experts_held - 1``) of ``g_i E_i(h)``, plus the
    shared expert's SwiGLU. What the absent experts would add is left out,
    as in the program.

Departures from the source, the program's: the input is the torso's output
and the one-hot last action through a bias-free projection and an RMSNorm
where the token embedding stood; the router reads its input less its mean
over the call's positions (the learner's form; acting, which this file does
not compute, subtracts a stored mean); b enters the choice only; no
``seq_aux`` loss, no multi-token prediction.

Weights are the program's parameter tree, so both sides run the same seeded
weights; this file only reads arrays out of it.
"""

import dataclasses
import functools
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import r2d2


def _norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, positions, theta):
    """x (..., S, d) or (..., S, H, d); positions (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    if x.ndim == 4:
        angle = angle[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]) * (h @ p["up_proj"])) \
        @ p["down_proj"]


def _attention(h, stored, p, core):
    """h (B, T, D); stored (B, M, c + rope) -> (B, T, D)."""
    dc, dn = core["kv_lora_rank"], core["qk_nope_head_dim"]
    dr, m = core["qk_rope_head_dim"], core["memory_len"]
    t = h.shape[1]
    q = jnp.einsum("btd,dhe->bthe", h, p["q_proj"])
    kva = h @ p["kv_a_proj_with_mqa"]
    own = jnp.concatenate(
        [_norm(kva[..., :dc], p["kv_a_layernorm"]["weight"],
               core["rms_norm_eps"]), kva[..., dc:]], axis=-1)
    keys = jnp.concatenate([stored, own], axis=1)          # (B, M + T, .)
    key_positions = jnp.arange(-m, t)
    expanded = jnp.einsum("bsc,che->bshe", keys[..., :dc], p["kv_b_proj"])
    k_nope, v = expanded[..., :dn], expanded[..., dn:]
    k_rope = _rotate(keys[..., dc:], key_positions, core["rope_theta"])
    q_rope = _rotate(q[..., dn:], jnp.arange(t), core["rope_theta"])
    scores = (jnp.einsum("bthe,bshe->bhts", q[..., :dn], k_nope)
              + jnp.einsum("bthe,bse->bhts", q_rope, k_rope)) \
        / jnp.sqrt(float(dn + dr))
    visible = ((key_positions[None, None, :] <= jnp.arange(t)[None, :, None])
               & jnp.concatenate(
                   [jnp.any(stored != 0, axis=-1),
                    jnp.ones(own.shape[:2], bool)], axis=1)[:, None, :])
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhts,bshe->bthe", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bthe,hed->btd", out, p["o_proj"])


def _experts(h, p, core):
    """h (N, D) -> (N, D): the held share of the routed experts, and the
    shared expert."""
    width = core["moe_intermediate_size"]
    centred = h - jax.lax.stop_gradient(h.mean(axis=0))
    s = jax.nn.sigmoid(centred @ p["gate"])                 # (N, routed)
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                              core["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * core["routed_scaling_factor"]
    out = _swiglu(h, p["shared_experts"])
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
    width = core["kv_lora_rank"] + core["qk_rope_head_dim"]
    stored = hidden.astype(jnp.float32).reshape(
        b, core["num_hidden_layers"], core["memory_len"], width)
    x = _norm(x_seq @ p["input_proj"], p["input_norm"]["weight"],
              core["rms_norm_eps"])
    for i in range(core["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        x = x + _attention(
            _norm(x, layer["input_layernorm"], core["rms_norm_eps"]),
            stored[:, i], layer["self_attn"], core)
        h = _norm(x, layer["post_attention_layernorm"],
                  core["rms_norm_eps"]).reshape(b * t, -1)
        if i < core["first_k_dense_replace"]:
            out = _swiglu(h, layer["mlp"])
        else:
            out = _experts(h, layer["mlp"], core)
        x = x + out.reshape(b, t, -1)
    return _norm(x, p["norm"]["weight"], core["rms_norm_eps"])


def unroll_q(params: Dict[str, Any], frames, last_action, hidden, *,
             stack, strides, dueling, core):
    """Q for every position of every window, as ``r2d2.unroll_q`` gives it
    (same torso, same head), with this core where the LSTM stands.

    frames (B, T + stack - 1, H, W) uint8; last_action (B, T) int32, -1 for
    none; hidden (B, 2, half) the packed latent cache. Returns (B, T, A)."""
    p = params["params"]
    b, t = last_action.shape
    f = frames.astype(jnp.float32) / 255.0
    obs = jnp.stack([f[:, k:k + t] for k in range(stack)], axis=-1)
    x = obs.reshape((b * t,) + obs.shape[2:])
    for i, stride in enumerate(strides):
        conv = p["torso"][f"Conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, conv["kernel"], (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + conv["bias"]
        x = jnp.maximum(x, 0.0)
    dense = p["torso"]["Dense_0"]
    latent = x.reshape(b * t, -1) @ dense["kernel"] + dense["bias"]

    actions = p["mem_core"]["input_proj"].shape[0] - latent.shape[-1]
    one_hot = (last_action[..., None] == jnp.arange(actions)).astype(
        jnp.float32)
    x_seq = jnp.concatenate([latent.reshape(b, t, -1), one_hot], axis=-1)
    hs = core_forward(p["mem_core"], x_seq, hidden, core).reshape(b * t, -1)

    head = p["head"]

    def mlp(first, second):
        z = jnp.maximum(hs @ head[first]["kernel"] + head[first]["bias"], 0.0)
        return z @ head[second]["kernel"] + head[second]["bias"]

    adv = mlp("adv_hidden", "adv_out")
    q = adv
    if dueling:
        q = mlp("val_hidden", "val_out") + adv - adv.mean(-1, keepdims=True)
    return q.reshape(b, t, -1)


def _loss_outputs(core: Dict[str, Any]):
    """``r2d2.loss_outputs`` (the same loss: n-step double-Q target, value
    rescaling, priorities) reading its Q from this file's ``unroll_q``: the
    function's code with ``unroll_q`` bound to ours in its globals."""
    fn = r2d2.loss_outputs
    rebound = types.FunctionType(
        fn.__code__, {**vars(r2d2), "unroll_q": functools.partial(
            unroll_q, core=core)}, fn.__name__, fn.__defaults__,
        fn.__closure__)
    rebound.__kwdefaults__ = fn.__kwdefaults__
    return rebound


def from_config(cfg):
    """What every reference module gives the comparison (``check.py``):
    ``fn(params, target_params, batch fields) -> loss_outputs`` at the sizes
    of the program's ``Config``, in true float32 products."""
    loss_outputs = _loss_outputs(dataclasses.asdict(cfg.network.core))
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
