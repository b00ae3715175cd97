"""Plain float32 reference of the R2D2 loss with the ``kda_mla_moe`` memory
core: the torso, the dueling head and the loss of ``reference/r2d2.py`` (as
``reference/r2d2_mla_moe.py`` spells them around a core, imported), and
between them a stack of Kimi-Linear-48B-A3B's layers written from the
source's ``config.json``
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/
config.json, ``model_type: kimi_linear``) and the Kimi Linear technical
report (arXiv:2510.26692) in straightforward ``jax.numpy``: a Python loop
over layers, Kimi Delta Attention as its recurrence with one ``lax.scan``
step a position (no chunks, no kernel), dense ``einsum``s, a loop over the
experts held with a mask. No bf16, and none of the program's functions: the
state row's layout is written out here again.

Per layer on the residual stream x (d wide), pre-norm RMSNorm, and a final
RMSNorm before the head:

  * x += KDA(RMSNorm(x)) in the layers ``layer_types`` names "kda": H heads
    of e; ``q~, k~, v~ = W_q h, W_k h, W_v h``; each through a causal
    depthwise convolution of ``short_conv_kernel_size`` taps over the
    stored last inputs and the window, then silu; q and k divided by their
    norm per head (+1e-6 under the root), q by sqrt(e);
    ``g = -exp(A_log) softplus(W_fb W_fa h + dt_bias)``, ``beta =
    sigmoid(W_b h)``; per position ``S <- S * exp(g)`` (each key channel's
    row), ``S <- S + beta k (v - S^T k)^T``, ``o = S^T q``; the output
    ``W_o (RMSNorm_e(o) * sigmoid(W_gb W_ga h + b_g))``.
  * x += MLA(RMSNorm(x)) in the layers named "full_attention":
    ``reference/r2d2_mla_moe.py``'s latent attention with no rotation of
    the 64 shared dimensions (``mla_use_nope``), computed in blocks of
    ``QUERY_BLOCK`` queries where the window is longer, which changes no
    value.
  * x += FFN(RMSNorm(x)): the first ``first_k_dense_replace`` layers
    ``W_2(silu(W_1 h) * W_3 h)``; the others ``r2d2_mla_moe``'s sigmoid
    router over all routed experts with the shared expert and the held
    share.

The state row a window leaves, per layer in order: a KDA layer's S at the
last position (H, e, e) and the last taps - 1 inputs of [q~ | k~ | v~]
before the convolution; an MLA layer's last ``memory_len`` latent rows
(c after its norm | k_r). ``from_config`` gives it beside the loss, and
``parts`` splits such a row by part and layer.

Departures from the source are the program's (``benchmarks/configs/
kimi-linear-core.json``, ``assumed``). Weights are the program's parameter
tree; this file only reads arrays out of it.
"""

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import r2d2_mla_moe
from benchmarks.reference.r2d2_lfm2 import _rebind
from benchmarks.reference.r2d2_mla_moe import _experts, _norm, _swiglu

# queries a block of the latent attention takes at once where the window
# is longer
QUERY_BLOCK = 32


def layout(core: Dict[str, Any]) -> List[Tuple[str, List[Tuple[str, int]]]]:
    """Per layer in order, its kind and its parts as (part, floats)."""
    width = core["kda_num_heads"] * core["kda_head_dim"]
    kda = [("kda_state", core["kda_num_heads"] * core["kda_head_dim"] ** 2),
           ("conv_tail", (core["short_conv_kernel_size"] - 1) * 3 * width)]
    mla = [("latent_cache", core["memory_len"]
            * (core["kv_lora_rank"] + core["qk_rope_head_dim"]))]
    return [(kind, kda if kind == "kda" else mla)
            for kind in core["layer_types"]]


def parts(row, core: Dict[str, Any]) -> Dict[str, List[Any]]:
    """A state row (B, 2, half) split into {part: [its array (B, floats) in
    each layer that has it]}."""
    flat = jnp.asarray(row).reshape(row.shape[0], -1)
    found: Dict[str, List[Any]] = {}
    at = 0
    for _, pieces in layout(core):
        for name, floats in pieces:
            found.setdefault(name, []).append(flat[:, at:at + floats])
            at += floats
    return found


def _kda(h, state, tail, p, core):
    """h (B, T, d); state (B, H, e, e); tail (B, taps - 1, 3 H e) -> (out
    (B, T, d), the state and the tail the window leaves). One sequence at a
    time (``lax.map``), which changes no value and holds one sequence's
    activations at once."""
    heads, e = core["kda_num_heads"], core["kda_head_dim"]
    taps, eps = core["short_conv_kernel_size"], core["rms_norm_eps"]
    width = heads * e

    def one(args):
        h, state, tail = args
        t = h.shape[0]

        def conv(i, name):
            seq = jnp.concatenate([tail[:, i * width:(i + 1) * width],
                                   h @ p[f"{name}_proj"]], axis=0)
            w = p[f"{name}_conv1d"]
            out = jax.nn.silu(sum(w[:, j] * seq[j:j + t]
                                  for j in range(taps)))
            return out.reshape(t, heads, e), seq[t:]

        (q, q_tail), (k, k_tail), (v, v_tail) = (
            conv(i, name) for i, name in enumerate("qkv"))
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            / jnp.sqrt(float(e))
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            (h @ p["f_a_proj"] @ p["f_b_proj"] + p["dt_bias"]).reshape(
                t, heads, e))
        beta = jax.nn.sigmoid(h @ p["b_proj"])              # (T, H)

        def step(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            s = s * jnp.exp(g_t)[..., None]
            s = s + b_t[:, None, None] * k_t[..., :, None] * (
                v_t - jnp.einsum("hd,hdv->hv", k_t, s))[..., None, :]
            return s, jnp.einsum("hd,hdv->hv", q_t, s)

        state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
        gate = (h @ p["g_a_proj"] @ p["g_b_proj"] + p["g_b_bias"]).reshape(
            t, heads, e)
        out = jnp.einsum("the,hed->td", _norm(o, p["o_norm"], eps)
                         * jax.nn.sigmoid(gate), p["o_proj"])
        return out, state, jnp.concatenate([q_tail, k_tail, v_tail], -1)

    return jax.lax.map(one, (h, state, tail))


def _latent_attention(h, stored, p, core):
    """h (B, T, d); stored (B, M, c + r) -> (out (B, T, d), the window's
    rows (B, T, c + r)); NoPE: the r dimensions are not rotated."""
    dc, dn = core["kv_lora_rank"], core["qk_nope_head_dim"]
    dr, m = core["qk_rope_head_dim"], core["memory_len"]
    b, t, _ = h.shape
    q = jnp.einsum("btd,dhe->bthe", h, p["q_proj"])
    kva = h @ p["kv_a_proj_with_mqa"]
    own = jnp.concatenate(
        [_norm(kva[..., :dc], p["kv_a_layernorm"]["weight"],
               core["rms_norm_eps"]), kva[..., dc:]], axis=-1)
    keys = jnp.concatenate([stored, own], axis=1)           # (B, M + T, .)
    expanded = jnp.einsum("bsc,che->bshe", keys[..., :dc], p["kv_b_proj"])
    k_nope, v = expanded[..., :dn], expanded[..., dn:]
    positions = jnp.arange(-m, t)
    filled = jnp.concatenate([jnp.any(stored != 0, axis=-1),
                              jnp.ones((b, t), bool)], axis=1)

    def block(at):
        scores = (jnp.einsum("bqhe,bshe->bhqs", q[:, at, :, :dn], k_nope)
                  + jnp.einsum("bqhe,bse->bhqs", q[:, at, :, dn:],
                               keys[..., dc:])) / jnp.sqrt(float(dn + dr))
        visible = filled[:, None, :] & (positions[None, :]
                                        <= at[:, None])[None]
        scores = jnp.where(visible[:, None], scores, -jnp.inf)
        out = jnp.einsum("bhqs,bshe->bqhe", jax.nn.softmax(scores, -1), v)
        return jnp.einsum("bqhe,hed->bqd", out, p["o_proj"])

    if t > QUERY_BLOCK and t % QUERY_BLOCK == 0:
        out = jax.lax.map(block, jnp.arange(t).reshape(-1, QUERY_BLOCK))
        return out.transpose(1, 0, 2, 3).reshape(b, t, -1), own
    return block(jnp.arange(t)), own


def core_forward_state(p: Dict[str, Any], x_seq, hidden,
                       core: Dict[str, Any]):
    """x_seq (B, T, D_in); hidden (B, 2, half) -> ((B, T, hidden_size), the
    state row the window leaves (B, 2, half))."""
    b, t, _ = x_seq.shape
    eps = core["rms_norm_eps"]
    heads, e = core["kda_num_heads"], core["kda_head_dim"]
    row = hidden.astype(jnp.float32).reshape(b, -1)
    x = _norm(x_seq @ p["input_proj"], p["input_norm"]["weight"], eps)
    new, at = [], 0
    for i, (kind, pieces) in enumerate(layout(core)):
        layer = p[f"layers_{i}"]
        h = _norm(x, layer["input_layernorm"], eps)
        stored = [row[:, at + sum(f for _, f in pieces[:j]):
                      at + sum(f for _, f in pieces[:j + 1])]
                  for j in range(len(pieces))]
        at += sum(f for _, f in pieces)
        if kind == "kda":
            out, state, tail = _kda(
                h, stored[0].reshape(b, heads, e, e),
                stored[1].reshape(b, core["short_conv_kernel_size"] - 1, -1),
                layer["self_attn"], core)
            new += [state.reshape(b, -1), tail.reshape(b, -1)]
        else:
            cache = stored[0].reshape(b, core["memory_len"], -1)
            out, own = _latent_attention(h, cache, layer["self_attn"], core)
            new.append(jnp.concatenate([cache, own], axis=1)[
                :, -core["memory_len"]:].reshape(b, -1))
        x = x + out
        h = _norm(x, layer["post_attention_layernorm"], eps).reshape(b * t,
                                                                     -1)
        if i < core["first_k_dense_replace"]:
            out = _swiglu(h, layer["mlp"])
        else:
            out = _experts(h, layer["mlp"], core)
        x = x + out.reshape(b, t, -1)
    return (_norm(x, p["norm"]["weight"], eps),
            jnp.concatenate(new, axis=1).reshape(b, 2, -1))


def from_config(cfg):
    """What every reference module gives the comparison (``check.py``):
    ``fn(params, target_params, batch fields) -> loss_outputs`` at the sizes
    of the program's ``Config``, in true float32 products; beside them
    ``state_row``, the row the online window leaves (B, 2, half), which
    ``runners/learner_8k_state.py`` holds the program's to."""
    core = dataclasses.asdict(cfg.network.core)
    static = dict(
        frame_hw=(cfg.env.frame_height, cfg.env.frame_width),
        stack=cfg.env.frame_stack,
        strides=tuple(stride for _, _, stride in cfg.network.conv_layers),
        dueling=cfg.network.use_dueling, double=cfg.network.use_double,
        n_step=cfg.sequence.forward_steps,
        rescale_eps=cfg.optim.value_rescale_eps, eta=cfg.optim.priority_eta)

    def run(params, target_params, batch):
        found = []

        def forward(p, x_seq, hidden, core):
            y, row = core_forward_state(p, x_seq, hidden, core)
            found.append(row)
            return y

        unroll_q = _rebind(r2d2_mla_moe.unroll_q, dict(
            vars(r2d2_mla_moe), core_forward=forward))
        loss_outputs = _rebind(r2d2_mla_moe.r2d2.loss_outputs, dict(
            vars(r2d2_mla_moe.r2d2),
            unroll_q=functools.partial(unroll_q, core=core)))
        with jax.default_matmul_precision("highest"):
            out = loss_outputs(params, target_params, batch, **static)
        return {**out, "state_row": found[0]}
    return jax.jit(run)
