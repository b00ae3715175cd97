"""A stack of DeepSeek-V3-form layers as the agent's memory core: multi-head
latent attention (MLA) over a stored latent cache, then a dense SwiGLU (the
first ``first_k_dense_replace`` layers) or a mixture of experts of which
this chip holds ``experts_held`` of ``n_routed_experts``.

Equations, from the source's ``config.json`` (``model_type: deepseek_v3``,
``q_lora_rank: null``), per layer on the residual stream x (``hidden_size``),
pre-norm, and a final RMSNorm before the head::

    x += Attn(RMSNorm(x));   x += FFN(RMSNorm(x))

    Attn(h):  q = W_q h             -> per head [q_nope | q_rope]
              [c | k_r] = W_kva h;  c <- RMSNorm(c)
              [k_nope | v] per head = W_kvb c
              q_rope, k_r <- RoPE(theta); k_r is shared by all heads
              softmax((q_nope.k_nope + q_rope.k_r) / sqrt(d_nope + d_rope)) v
              W_o over the heads' values; no biases
    FFN dense:  W_down(silu(W_gate h) * W_up h)
    FFN MoE:    s = sigmoid(W_r (h - m)) over all routed experts
                (m: the mean of h over positions, see below)
                chosen = top-k of s + b   (b: correction bias, noaux_tc)
                g_i = s_i / (sum_chosen s + 1e-20) * routed_scaling_factor
                sum_{i chosen and held} g_i E_i(h) + Shared(h)

The recurrent state is the latent cache: per layer the last ``memory_len``
positions' (c after its norm, k_r before its rotation), oldest first, packed
into the float32 row ``(2, state_half)``. RoPE depends on offsets only, so
slot j is rotated at use at position j - memory_len and window step t at
position t. A slot whose values are all zero is empty and masked
(``init_state`` is zeros: an episode's first step attends to itself alone).
A window attends to the stored cache as a prefix, with no gradient into it
(R2D2's stored state, Transformer-XL's memory), and causally to itself; at
T = 1 the cache shifts by one. k_nope and v are re-expanded from c by W_kvb
wherever they are used.

Held experts: the router keeps its published width and its experts per
token; this chip computes the experts ``expert_offset .. expert_offset +
experts_held - 1`` for the (position, expert) pairs that fall on them and
leaves out what the others would add. No pair is dropped and there is no
capacity factor: all pairs are sorted by expert (pairs on absent experts
last), and the held ones go through grouped matrix products a chunk of
rows at a time, as many chunks as there are held pairs
(``held_experts_ffn``). The way back follows the same pairs: each chunk adds
its rows to their positions' float32 sums as it goes (``add_rows``), forward
and backward, so no array has a row for every pair and a chip that holds an
eighth of the experts moves an eighth of the rows.

Precision: matrix products take ``dtype`` operands (bf16 on a TPU) and
accumulate in float32; the residual stream, the norms, the router's scores,
the rotation, the softmax and the experts' combine are float32.

The source's forms that this file implements, and no other (its
``config.json`` spells them ``q_lora_rank: null``, ``scoring_func:
"sigmoid"``, ``topk_method: "noaux_tc"``, ``n_group: 1``, ``topk_group: 1``,
``norm_topk_prob: true``, ``moe_layer_freq: 1``): the query has no low-rank
factor, the router scores by sigmoid, chooses over one group of all experts
with the correction bias, normalises the chosen weights, and every layer
past the dense ones is an expert layer. They are not options here.

The router reads what varies between positions. An agent's stream is not a
language model's: the torso's latents are rectified, so every position
shares a large common part (nine tenths of the router's input on frames of
noise), every expert's score then moves with it, all positions choose the
same experts, and the router's gradient, which is that common part times a
sum over positions, drives the collapse on with every optimizer step
(PERF.md, Findings, PR 27). So the router's input is h less its mean m over
positions. With ``window_stats`` (the learner) m is the mean over the call's
own positions, and comes back among the counters; the train step stores it
in ``router_input_mean``, which is what a call without ``window_stats``
(acting) subtracts. No gradient flows into m.

Departures from the source, all written in the benchmark's configuration
file too: the router's centred input (above); the correction bias b is held
among the parameters but only the choice reads it, so no gradient reaches
it, and no balance rule updates it (zeros at init); no ``seq_aux`` loss; no
multi-token prediction; the input is the torso's latent and the one-hot
last action through a bias-free projection and an RMSNorm where the token
embedding stood.
"""

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.config import CoreConfig
from r2d2_tpu.ops.pallas_kernels import add_rows

# the source family's ``initializer_range``; the projections that write
# into the residual stream (o_proj, every down_proj) are drawn narrower by
# sqrt(2 x the published depth), the residual scaling of GPT-2 and Megatron,
# so that the stream keeps its size through the layers
_INIT = nn.initializers.normal(0.02)
_PUBLISHED_DEPTH = 27
_INIT_OUT = nn.initializers.normal(0.02 / (2 * _PUBLISHED_DEPTH) ** 0.5)
_F32 = jnp.float32


def _matmul(spec: str, x, w, dtype, out_dtype=None):
    """``einsum`` of ``dtype`` operands accumulated in float32."""
    return jnp.einsum(spec, x.astype(dtype), w.astype(dtype),
                      preferred_element_type=_F32).astype(out_dtype or dtype)


def rms_norm(x, weight, eps: float):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rope(x, positions, theta: float):
    """Rotate the last axis of ``x`` (..., S, d) or (..., S, H, d) at
    ``positions`` (S,): pairs (i, i + d/2) by positions * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) / half)
    angle = positions.astype(_F32)[:, None] * inv[None, :]        # (S, half)
    if x.ndim == 4:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(_F32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class _Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, w, self.eps)


class LatentAttention(nn.Module):
    """Pre-norm MLA over [stored cache | window]: ``x`` the residual stream,
    ``norm_weight`` the layer's input norm. Returns the attention output
    (B, T, hidden) float32 and the window's cache rows (B, T, c + rope)
    float32."""
    core: CoreConfig
    dtype: Any

    @nn.compact
    def __call__(self, x, norm_weight, mem):
        c, dt = self.core, self.dtype
        h = rms_norm(x, norm_weight, c.rms_norm_eps)
        heads, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                             c.qk_rope_head_dim, c.v_head_dim)
        d, dc, m = c.hidden_size, c.kv_lora_rank, c.memory_len
        t = h.shape[1]
        w_q = self.param("q_proj", _INIT, (d, heads, dn + dr))
        w_kva = self.param("kv_a_proj_with_mqa", _INIT, (d, dc + dr))
        w_kvb = self.param("kv_b_proj", _INIT, (dc, heads, dn + dv))
        w_o = self.param("o_proj", _INIT_OUT, (heads, dv, d))

        q = _matmul("btd,dhe->bthe", h, w_q, dt)
        kva = _matmul("btd,de->bte", h, w_kva, dt, _F32)
        latent = _Norm(c.rms_norm_eps, name="kv_a_layernorm")(kva[..., :dc])
        rows = jnp.concatenate([latent, kva[..., dc:]], axis=-1)  # (B,T,dc+dr)

        mem = jax.lax.stop_gradient(mem.astype(_F32))             # (B,M,dc+dr)
        keys = jnp.concatenate([mem, rows], axis=1)               # (B,M+T,.)
        key_pos = jnp.arange(-m, t)
        kvb = _matmul("bsc,che->bshe", keys[..., :dc], w_kvb, dt)
        k_rot = rope(keys[..., dc:], key_pos, c.rope_theta)       # (B,S,dr)
        q_rot = rope(q[..., dn:], jnp.arange(t), c.rope_theta)    # (B,T,H,dr)
        scores = (_matmul("bthe,bshe->bhts", q[..., :dn], kvb[..., :dn], dt,
                          _F32)
                  + _matmul("bthe,bse->bhts", q_rot, k_rot, dt, _F32))
        scores = scores * (dn + dr) ** -0.5
        # a window step sees the filled slots of the stored cache and the
        # window up to itself
        filled = jnp.any(mem != 0, axis=-1)                       # (B,M)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        seen = jnp.concatenate(
            [jnp.broadcast_to(filled[:, None, :], (h.shape[0], t, m)),
             jnp.broadcast_to(causal[None], (h.shape[0], t, t))], axis=-1)
        scores = jnp.where(seen[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        values = _matmul("bhts,bshe->bthe", probs, kvb[..., dn:], dt)
        return _matmul("bthe,hed->btd", values, w_o, dt, _F32), rows


class SwiGLU(nn.Module):
    """``h`` (N, d), or the residual stream with the norm's weight to put
    in front (``eps`` its epsilon)."""
    width: int
    dtype: Any
    eps: float = 0.0

    @nn.compact
    def __call__(self, h, norm_weight=None):
        if norm_weight is not None:
            h = rms_norm(h, norm_weight, self.eps)
        d = h.shape[-1]
        gate = self.param("gate_proj", _INIT, (d, self.width))
        up = self.param("up_proj", _INIT, (d, self.width))
        down = self.param("down_proj", _INIT_OUT, (self.width, d))
        a = (jax.nn.silu(_matmul("nd,df->nf", h, gate, self.dtype
                                 ).astype(_F32))
             * _matmul("nd,df->nf", h, up, self.dtype).astype(_F32))
        return _matmul("nf,fd->nd", a, down, self.dtype, _F32)


def route(scores, bias, core: CoreConfig):
    """(chosen (N, k) int32, weights (N, k) float32) from the router's
    sigmoid scores (N, routed): the top-k of scores + bias, weighted by
    their own scores normalised over the chosen and scaled."""
    _, chosen = jax.lax.top_k(scores + bias, core.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
               * core.routed_scaling_factor)
    return chosen, weights


def grouped_matmul(rows, weights, group_sizes, dtype):
    """Rows sorted by group times their group's matrix: rows (M, k),
    weights (G, k, n), ``group_sizes`` (G,) -> (M, n) in ``dtype``; rows
    past the groups' total are undefined. ``jax.lax.ragged_dot``: XLA's own
    grouped product on the TPU, with XLA's own backward (PERF.md, Findings,
    PR 27 says why not the megablox kernels)."""
    return jax.lax.ragged_dot(rows.astype(dtype), weights.astype(dtype),
                              group_sizes, preferred_element_type=_F32
                              ).astype(dtype)


# Rows of sorted pairs a grouped product takes at a time (held_experts_ffn).
# A step's work moves in whole chunks, so the size is chosen for the pairs a
# layer expects at the benchmark's batch (8,000 positions x 6 x 8/64 = 6,000):
# three chunks hold them with a quarter to spare and two fall short by a
# seventh, so a router a few per cent off its expectation costs the same.
CHUNK_ROWS = 2560


def _chunk_ffn(x, weight, gate_up, down, sizes, live):
    """The experts' SwiGLU on one chunk of sorted rows, two grouped
    products, each row times its pair's routing weight. ``live`` (rows, 1)
    marks the rows that stand for a pair on a held expert; a grouped
    product leaves the rows past its groups undefined, so those read zero
    and give no gradient."""
    width = down.shape[1]
    x = jnp.where(live, x, 0)
    with jax.named_scope("moe_experts"):
        gu = grouped_matmul(x, gate_up, sizes, x.dtype)
    act = (jax.nn.silu(gu[:, :width].astype(_F32))
           * gu[:, width:].astype(_F32)).astype(x.dtype)
    with jax.named_scope("moe_experts"):
        out = grouped_matmul(act, down, sizes, _F32)
    return jnp.where(live, out * weight[:, None], 0).astype(x.dtype)


def _chunk_of(i, chunk: int, h, order, pair_weight, group_sizes):
    """Chunk ``i`` of the sorted pairs: (its pairs, the position each
    row's sum goes to, ``len(h)`` where the row stands for no pair on a
    held expert; its positions' rows of ``h``, its pairs' weights, the
    groups' sizes inside it, its live rows)."""
    lo = i * chunk
    pairs = jax.lax.dynamic_slice_in_dim(order, lo, chunk)
    ends = jnp.cumsum(group_sizes)
    sizes = (jnp.clip(ends, lo, lo + chunk)
             - jnp.clip(ends - group_sizes, lo, lo + chunk))
    live = lo + jnp.arange(chunk) < ends[-1]
    n = h.shape[0]
    at = pairs % n
    with jax.named_scope("moe_dispatch"):
        x = h[at]
        w = pair_weight[jnp.minimum(pairs, pair_weight.shape[0] - 1)]
    return pairs, jnp.where(live, at, n), x, w, sizes, live[:, None]


def _live_chunks(group_sizes, chunk: int):
    return (jnp.sum(group_sizes) + chunk - 1) // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def held_experts_ffn(h, order, pair_weight, gate_up, down, group_sizes,
                     chunk: int):
    """The held experts' weighted SwiGLU, summed to the positions.

    ``h`` (N, d) the positions; ``order`` (M,) the pairs sorted by expert,
    the pairs on absent experts last, padded to whole chunks with numbers
    past the pairs' (pair = choice * N + position); ``pair_weight``
    (N * top_k,) the pairs' routing weights in pair order; gate_up
    (G, d, 2f), down (G, f, d); ``group_sizes`` (G,) the pairs on each held
    expert. Returns each position's sum over its pairs on held experts
    (N, d) float32, and the number of rows the chunks it walked took in as
    pairs.

    The sorted pairs are taken ``chunk`` at a time, and only as many chunks
    as hold a pair on a held expert (a loop with a dynamic trip count): a
    chunk gathers its positions' rows and its pairs' weights, runs the two
    grouped products and adds its rows to their positions' sums
    (``add_rows``). So the work follows the pairs that are here, there and
    back, whatever the router's skew, with static shapes and no pair
    dropped: no array has a row for every pair. The backward is its own: it
    walks the same chunks, gathers a chunk's positions' rows of the sum's
    gradient, recomputes the chunk's activation, adds the weights'
    gradients into one float32 accumulator in place and the rows'
    gradients to their positions as the forward does."""
    # the weights come as the parameters are kept (float32) and are cast
    # here, once a call, so that their gradient goes back uncast
    gate_up, down = gate_up.astype(h.dtype), down.astype(h.dtype)
    # the second product takes float32 operands: cast once here; left to
    # the chunk's own cast, XLA makes it again for every chunk (0.17 ms
    # each at the cell's sizes; PERF.md, Findings, PR 30)
    down = down.astype(_F32)

    def one(i, carry):
        total, covered = carry
        _, pos, x, w, sizes, live = _chunk_of(i, chunk, h, order,
                                              pair_weight, group_sizes)
        rows = _chunk_ffn(x, w, gate_up, down, sizes, live)
        with jax.named_scope("moe_combine"):
            total = add_rows(total, rows, pos)
        return total, covered + jnp.sum(live, dtype=jnp.int32)

    return jax.lax.fori_loop(
        0, _live_chunks(group_sizes, chunk), one,
        (jnp.zeros(h.shape, _F32), jnp.zeros((), jnp.int32)))


def _held_experts_fwd(h, order, pair_weight, gate_up, down, group_sizes,
                      chunk):
    return (held_experts_ffn(h, order, pair_weight, gate_up, down,
                             group_sizes, chunk),
            (h, order, pair_weight, gate_up, down, group_sizes))


def _held_experts_bwd(chunk, res, g):
    g, _ = g                                # the count carries no gradient
    h, order, pair_weight, gate_up, down, group_sizes = res
    kept = gate_up.dtype, down.dtype
    gate_up, down = gate_up.astype(h.dtype), down.astype(h.dtype)

    def one(i, carry):
        dh, dw, dw1, dw2 = carry
        pairs, pos, x, w, sizes, live = _chunk_of(i, chunk, h, order,
                                                  pair_weight, group_sizes)
        with jax.named_scope("moe_combine"):
            # a row's gradient is its position's
            gi = g[pairs % g.shape[0]].astype(h.dtype)
        _, vjp = jax.vjp(
            lambda x, w, w1, w2: _chunk_ffn(x, w, w1, w2, sizes, live),
            x, w, gate_up, down)
        dxi, dwi, dw1i, dw2i = vjp(gi)
        with jax.named_scope("moe_dispatch"):
            dh = add_rows(dh, dxi, pos)
            # a permutation's numbers and, past them, the padding's
            dw = dw.at[pairs].set(dwi, mode="drop", unique_indices=True)
        return dh, dw, dw1 + dw1i.astype(_F32), dw2 + dw2i.astype(_F32)

    dh, dw, dw1, dw2 = jax.lax.fori_loop(
        0, _live_chunks(group_sizes, chunk), one,
        (jnp.zeros(h.shape, _F32), jnp.zeros_like(pair_weight),
         jnp.zeros(gate_up.shape, _F32), jnp.zeros(down.shape, _F32)))
    return (dh.astype(h.dtype), None, dw, dw1.astype(kept[0]),
            dw2.astype(kept[1]), None)


held_experts_ffn.defvjp(_held_experts_fwd, _held_experts_bwd)


class HeldExperts(nn.Module):
    """The routed experts this chip holds, for the pairs that fall on them:
    all N*k (position, expert) pairs are sorted by expert, the pairs on
    absent experts last; the held ones go through ``held_experts_ffn``,
    which gives each position's sum. Beside it the layer's counters:
    ``dropped``, the pairs the router put on held experts less the rows the
    chunks took in (none: there is no capacity to run out of), and
    ``rows_walked``, the sorted rows of the chunks that were walked."""
    core: CoreConfig
    dtype: Any

    @nn.compact
    def __call__(self, h, chosen, weights):
        c, dt = self.core, self.dtype
        n, d = h.shape
        k, held, width = (c.num_experts_per_tok, c.experts_held,
                          c.moe_intermediate_size)
        gate_up = self.param("gate_up_proj", _INIT, (held, d, 2 * width))
        down = self.param("down_proj", _INIT_OUT, (held, width, d))
        chunk = min(CHUNK_ROWS, n * k)
        padded = -(-n * k // chunk) * chunk

        with jax.named_scope("moe_dispatch"):
            # pairs numbered choice-major: pair = choice * N + position
            local = chosen.T.reshape(-1) - c.expert_offset        # (k*N,)
            on_held = (local >= 0) & (local < held)
            key = jnp.where(on_held, local, held)
            order = jnp.argsort(key, stable=True)
            # padding up to whole chunks: rows that stand for no pair
            order = jnp.concatenate([order, jnp.arange(
                n * k, padded, dtype=order.dtype)])
            group_sizes = jnp.sum(
                key[:, None] == jnp.arange(held)[None, :], axis=0,
                dtype=jnp.int32)
            pair_weight = jnp.where(on_held, weights.T.reshape(-1), 0.0)
        routed, covered = held_experts_ffn(h.astype(dt), order, pair_weight,
                                           gate_up, down, group_sizes, chunk)
        return routed, {
            "dropped": jnp.sum(on_held, dtype=jnp.int32) - covered,
            "rows_walked": (_live_chunks(group_sizes, chunk)
                            * chunk).astype(jnp.int32)}


class MoE(nn.Module):
    core: CoreConfig
    dtype: Any
    window_stats: bool

    @nn.compact
    def __call__(self, x, norm_weight):
        c = self.core
        b, t, d = x.shape
        flat = rms_norm(x, norm_weight, c.rms_norm_eps).reshape(b * t, d)
        with jax.named_scope("moe_router"):
            w_r = self.param("gate", _INIT, (d, c.n_routed_experts))
            # neither gets a gradient, so Adam leaves both where they are:
            # only the choice reads the bias, and the train step writes the
            # mean (store_router_means)
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (c.n_routed_experts,))
            stored = self.param("router_input_mean", nn.initializers.zeros,
                                (d,))
            mean = jax.lax.stop_gradient(
                jnp.mean(flat, axis=0) if self.window_stats else stored)
            scores = jax.nn.sigmoid(jnp.einsum(
                "nd,de->ne", flat - mean, w_r,
                precision=jax.lax.Precision.HIGHEST))
            chosen, weights = route(scores, bias, c)
        routed, walk = HeldExperts(c, self.dtype, name="experts")(
            flat, chosen, weights)
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(c.moe_intermediate_size * c.n_shared_experts,
                            self.dtype, name="shared_experts")(flat)
        with jax.named_scope("moe_combine"):
            out = (routed + shared).reshape(b, t, d)
        with jax.named_scope("moe_router"):
            share = scores / scores.sum(-1, keepdims=True)
            stats = {
                "chosen": jnp.sum(jax.nn.one_hot(
                    chosen, c.n_routed_experts, dtype=jnp.int32), axis=(0, 1)),
                "entropy": -jnp.mean(jnp.sum(share * jnp.log(share + 1e-30),
                                             axis=-1)),
                "input_mean": mean,
                **walk,
            }
        return out, stats


class Layer(nn.Module):
    """One pre-norm layer: ``x += Attn(norm(x)); x += FFN(norm(x))``."""
    core: CoreConfig
    dtype: Any
    dense: bool
    window_stats: bool

    @nn.compact
    def __call__(self, x, mem):
        c, dt = self.core, self.dtype
        d = x.shape[-1]
        w_in = self.param("input_layernorm", nn.initializers.ones, (d,))
        w_post = self.param("post_attention_layernorm", nn.initializers.ones,
                            (d,))
        with jax.named_scope("mla_attn"):
            attn, rows = LatentAttention(c, dt, name="self_attn")(x, w_in, mem)
            x = x + attn
        if self.dense:
            with jax.named_scope("dense_mlp"):
                b, t, _ = x.shape
                out = SwiGLU(c.intermediate_size, dt, c.rms_norm_eps,
                             name="mlp")(x.reshape(b * t, d), w_post)
                out, stats = out.reshape(b, t, d), None
        else:
            out, stats = MoE(c, dt, self.window_stats, name="mlp")(x, w_post)
        return x + out, rows, stats


class MlaMoeStack(nn.Module):
    """Input projection, the layers (each under ``jax.checkpoint``: the
    backward pass recomputes a layer's activations, which changes no value),
    the final norm; the state row in and out. The expert layers' routing
    counters are sown into the ``moe`` collection
    (``NetworkApply.apply_learner`` reads them)."""
    core: CoreConfig
    dtype: Any
    window_stats: bool

    @nn.compact
    def __call__(self, x_seq, state):
        c = self.core
        b, t = x_seq.shape[0], x_seq.shape[1]
        width = c.kv_lora_rank + c.qk_rope_head_dim
        mem = state.astype(_F32).reshape(b, c.num_hidden_layers, c.memory_len,
                                         width)
        w_in = self.param("input_proj", nn.initializers.lecun_normal(),
                          (x_seq.shape[-1], c.hidden_size))
        # the stream starts at unit size, as after an embedding's norm
        x = _Norm(c.rms_norm_eps, name="input_norm")(
            _matmul("btd,de->bte", x_seq, w_in, self.dtype, _F32))
        new_mem, stats = [], []
        for i in range(c.num_hidden_layers):
            x, rows, s = nn.remat(Layer)(
                c, self.dtype, dense=i < c.first_k_dense_replace,
                window_stats=self.window_stats, name=f"layers_{i}")(
                    x, mem[:, i])
            new_mem.append(jnp.concatenate([mem[:, i], rows],
                                           axis=1)[:, -c.memory_len:])
            if s is not None:
                stats.append(s)
        if stats:
            self.sow("moe", "counters", jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *stats))
        y = _Norm(c.rms_norm_eps, name="norm")(x).astype(self.dtype)
        return y, jnp.stack(new_mem, axis=1).reshape(b, 2, -1)


@dataclasses.dataclass(frozen=True)
class MlaMoeCore:
    core: CoreConfig
    dtype: Any
    scope = "mem_core"

    def __post_init__(self):
        c = self.core
        if (c.num_hidden_layers * c.memory_len
                * (c.kv_lora_rank + c.qk_rope_head_dim)) % 2:
            raise ValueError("network.core: the latent cache does not pack "
                             "into two equal halves of a state row")

    @property
    def state_half(self) -> int:
        c = self.core
        return (c.num_hidden_layers * c.memory_len
                * (c.kv_lora_rank + c.qk_rope_head_dim)) // 2

    @property
    def out_dim(self) -> int:
        return self.core.hidden_size

    def init_state(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch, 2, self.state_half), _F32)

    def unroll(self, x_seq: jnp.ndarray, state: jnp.ndarray,
               window_stats: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return MlaMoeStack(self.core, self.dtype, window_stats,
                           name=self.scope)(x_seq, state)


def moe_counters(mutated: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """The stack's sown counters out of ``apply(..., mutable=['moe'])``'s
    second result: {chosen (L_moe, routed), entropy (L_moe,), dropped
    (L_moe,), rows_walked (L_moe,), input_mean (L_moe, hidden)}, or {} for
    a stack without expert layers."""
    found = jax.tree_util.tree_leaves(
        mutated.get("moe", {}), is_leaf=lambda x: isinstance(x, tuple))
    return found[0][0] if found else {}


def store_router_means(params, means):
    """``params`` with each expert layer's ``router_input_mean`` set to its
    row of ``means`` (L_moe, hidden), the ``input_mean`` counter of a
    forward pass with ``window_stats``: what acting centres the router's
    input on from then on."""
    core = dict(params["params"][MlaMoeCore.scope])
    layers = sorted((name for name in core if name.startswith("layers_")
                     and "router_input_mean" in core[name]["mlp"]),
                    key=lambda name: int(name.rsplit("_", 1)[1]))
    for name, mean in zip(layers, means):
        core[name] = {**core[name], "mlp": {**core[name]["mlp"],
                                            "router_input_mean": mean}}
    return {**params, "params": {**params["params"],
                                 MlaMoeCore.scope: core}}
