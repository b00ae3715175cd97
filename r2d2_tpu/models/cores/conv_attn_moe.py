"""A stack of LFM2-MoE-form layers as the agent's memory core: each layer a
gated short convolution or grouped-query attention (``layer_types``), then a
dense SwiGLU (the first ``first_k_dense_replace`` layers) or a mixture of
experts of which this chip holds ``experts_held`` of ``n_routed_experts``.
Two kinds of stored state lie side by side in the one state row.

Equations, from the source's ``config.json``
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json,
``model_type: lfm2_moe``; ``conv_bias: false``, ``norm_topk_prob: true``,
``use_expert_bias: true``), per layer on the residual stream x
(``hidden_size``), pre-norm RMSNorm (``rms_norm_eps``, the source's
``norm_eps``), no biases, and a final RMSNorm before the head::

    x += Op(RMSNorm_operator(x));      x += FFN(RMSNorm_ffn(x))

    Op = conv (layer_types[i] == "conv"), the gated short convolution:
        [B | C | u] = W_in h                 W_in: d -> 3d, split in that order
        z_t   = B_t * u_t                    elementwise
        c_t   = sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j}
                                             depthwise, causal, L = conv_L_cache,
                                             w: (d, L); w[:, L-1] multiplies the
                                             current position; z before the
                                             sequence's start is zero
        Op(h)_t = W_out (C_t * c_t)          W_out: d -> d; no activation in Op
      stored state of the layer: z at the last L - 1 positions

    Op = full_attention, grouped-query attention:
        q = W_q h -> H heads x e;  k = W_k h, v = W_v h -> G heads x e
                                             (e = d / H, G = num_key_value_heads)
        q <- RMSNorm_e(q), k <- RMSNorm_e(k) per head, a learned weight of e
                                             each (q_layernorm, k_layernorm)
        q, k <- RoPE(theta)                  pairs (i, i + e/2) (rotate_half)
        query head n reads key/value head n // (H / G);
        softmax(q.k / sqrt(e)) v, causal;    W_o: d -> d
      stored state of the layer: memory_len positions of (k after its norm,
      before its rotation | v), oldest first

    FFN dense:  W_2 (silu(W_1 h) * W_3 h), width intermediate_size
    FFN MoE:    s = sigmoid(W_r (h - m))     over all routed experts, no bias
                                             (m: the mean of h over positions,
                                             a departure: experts.py)
                chosen = top-k of (s + b)    b: expert_bias, the choice only
                g_i = s_i / (sum_chosen s + 1e-6) * routed_scaling_factor
                sum_{i chosen and held} g_i E_i(h),
                E_i = W_2,i (silu(W_1,i h) * W_3,i h), width
                moe_intermediate_size; no shared expert

The state row: one float32 ``(2, state_half)`` row a sequence, the layers'
parts packed one after the other in layer order (``state_layout``): a conv
layer's part is (L - 1, d), an attention layer's (memory_len, 2 G e). Zeros
are the convolution's own left padding and an empty key/value window
(``init_state``); a key/value slot whose values are all zero is empty and
masked. RoPE depends on offsets only, so slot j is rotated at use at
position j - memory_len and window step t at position t (as ``mla_moe.py``
does for its latent cache). A window reads the stored parts as a prefix,
with no gradient into them (R2D2's stored state), and causally itself; at
T = 1 (the actor's step) a conv part shifts by one position and so does the
key/value window.

The experts are ``experts.py``'s (the router, the sort, the chunk walk, the
way back, the counters, the stored mean of the router's input), shared with
``mla_moe.py``; this file fixes what this source fixes: 1e-6 in the
weights' normalisation, no shared expert, the residual initialiser at the
published depth of 24, and ``CHUNK_ROWS``. The expert bias is held under
``experts.py``'s name for it (``e_score_correction_bias``).

Precision: matrix products take ``dtype`` operands (bf16 on a TPU) and
accumulate in float32; the residual stream, the norms, the gates' products
and the convolution, the rotation, the softmax, the router's scores and the
experts' combine are float32.

The source's forms that this file implements, and no other: sigmoid scores,
the expert bias in the choice, normalised weights, no shared expert, no
biases, QK-norm, every layer past the dense ones an expert layer. They are
not options here.

Departures from the source, all written in the benchmark's configuration
file too (benchmarks/configs/lfm2-core.json): the router's centred input;
the expert bias is zeros at init and nothing updates it (only the choice
reads it, so no gradient reaches it, and the program has no balance rule);
the input is the torso's latent and the one-hot last action through a
bias-free projection and an RMSNorm where the token embedding stood, the
dueling head where the vocabulary stood; the learner's window attends
further back than the actor's past ``memory_len`` + 1 steps (a window is
never cut to the actor's reach).

Scopes (the device trace's rows), inside ``mem_core``: ``short_conv``,
``gqa_attn``, ``dense_mlp``, and ``experts.py``'s ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``.
"""

import dataclasses
from typing import Any, List, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.config import CoreConfig
from r2d2_tpu.models.cores import experts
from r2d2_tpu.models.cores.experts import (INIT, SCOPE, Norm, SwiGLU, matmul,
                                           rms_norm, rope)

# the projections that write into the residual stream (a conv's and an
# attention's out_proj, every down_proj) are drawn for the published depth
_INIT_OUT = experts.residual_init(24)
_F32 = jnp.float32

# Rows of an overflow chunk: what ``experts.held_experts_ffn`` takes at a time
# of the sorted pairs past its first chunk (8,704 rows for the benchmark's
# 8,000 expected pairs). As in ``mla_moe.py``, where the sweep is: one layer
# forward and backward, 9.2 ms with none over, reads 14.3 | 14.2 | 14.4 with
# 9,000 pairs at chunks of 512 | 1,024 | 2,048 rows (the parent's walk: 19.6;
# my chip runs, PR 34), so the size is the other core's.
CHUNK_ROWS = 1024


class Part(NamedTuple):
    """One layer's part of the state row: the layer's number, its kind
    ("conv": the gated input's last positions; "full_attention": the
    key/value window), where the part starts among the row's ``2 x
    state_half`` floats, and its shape (positions, width)."""
    layer: int
    kind: str
    offset: int
    shape: Tuple[int, int]

    @property
    def size(self) -> int:
        return self.shape[0] * self.shape[1]


def state_layout(core: CoreConfig) -> List[Part]:
    """The state row's parts, in the order they are packed: the one place
    that says where a layer's stored state lies (the stack, the tests and
    the plain reference's adapter read it)."""
    head = core.hidden_size // core.num_attention_heads
    shapes = {"conv": (core.conv_L_cache - 1, core.hidden_size),
              "full_attention": (core.memory_len,
                                 2 * core.num_key_value_heads * head)}
    parts, offset = [], 0
    for i, kind in enumerate(core.layer_types):
        parts.append(Part(i, kind, offset, shapes[kind]))
        offset += parts[-1].size
    return parts


class ShortConv(nn.Module):
    """The gated short convolution over [stored positions | window]: ``h``
    (B, T, d) the normed stream, ``stored`` (B, L - 1, d) the gated input z
    of the L - 1 positions before the window. Returns the operator's output
    (B, T, d) float32 and z at the last L - 1 positions (B, L - 1, d)."""
    core: CoreConfig
    dtype: Any

    @nn.compact
    def __call__(self, h, stored):
        c, dt = self.core, self.dtype
        d, taps = c.hidden_size, c.conv_L_cache
        t = h.shape[1]
        w_in = self.param("in_proj", INIT, (d, 3, d))
        w = self.param("conv", INIT, (d, taps))
        w_out = self.param("out_proj", _INIT_OUT, (d, d))
        bcu = matmul("btd,dge->btge", h, w_in, dt).astype(_F32)
        z = bcu[:, :, 0] * bcu[:, :, 2]
        padded = jnp.concatenate(
            [jax.lax.stop_gradient(stored.astype(_F32)), z], axis=1)
        conv = sum(w[:, j] * padded[:, j:j + t] for j in range(taps))
        out = matmul("btd,de->bte", bcu[:, :, 1] * conv, w_out, dt, _F32)
        return out, padded[:, t:]


class GroupedQueryAttention(nn.Module):
    """Grouped-query attention with QK-norm over [stored window | window]:
    ``h`` (B, T, d) the normed stream, ``stored`` (B, M, 2 G e) the stored
    positions' (k after its norm, before its rotation | v). Returns the
    attention output (B, T, d) float32 and the window's own rows
    (B, T, 2 G e) float32."""
    core: CoreConfig
    dtype: Any

    @nn.compact
    def __call__(self, h, stored):
        c, dt = self.core, self.dtype
        d, heads, groups = (c.hidden_size, c.num_attention_heads,
                            c.num_key_value_heads)
        e, m = d // heads, c.memory_len
        b, t = h.shape[0], h.shape[1]
        w_q = self.param("q_proj", INIT, (d, groups, heads // groups, e))
        w_k = self.param("k_proj", INIT, (d, groups, e))
        w_v = self.param("v_proj", INIT, (d, groups, e))
        w_o = self.param("out_proj", _INIT_OUT,
                         (groups, heads // groups, e, d))

        q = Norm(c.rms_norm_eps, name="q_layernorm")(
            matmul("btd,dgne->btgne", h, w_q, dt, _F32))
        k = Norm(c.rms_norm_eps, name="k_layernorm")(
            matmul("btd,dge->btge", h, w_k, dt, _F32))
        v = matmul("btd,dge->btge", h, w_v, dt, _F32)
        rows = jnp.concatenate([k.reshape(b, t, -1), v.reshape(b, t, -1)],
                               axis=-1)                        # (B,T,2Ge)

        stored = jax.lax.stop_gradient(stored.astype(_F32))    # (B,M,2Ge)
        both = jnp.concatenate([stored, rows], axis=1)         # (B,M+T,.)
        keys = both[..., :groups * e].reshape(b, m + t, groups, e)
        values = both[..., groups * e:].reshape(b, m + t, groups, e)
        k_rot = rope(keys, jnp.arange(-m, t), c.rope_theta)    # (B,S,G,e)
        q_rot = rope(q.reshape(b, t, heads, e), jnp.arange(t),
                     c.rope_theta).reshape(q.shape)            # (B,T,G,n,e)
        scores = matmul("btgne,bsge->bgnts", q_rot, k_rot, dt, _F32)
        scores = scores * e ** -0.5
        # a window step sees the filled slots of the stored window and the
        # window up to itself
        filled = jnp.any(stored != 0, axis=-1)                 # (B,M)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        seen = jnp.concatenate(
            [jnp.broadcast_to(filled[:, None, :], (b, t, m)),
             jnp.broadcast_to(causal[None], (b, t, t))], axis=-1)
        scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = matmul("bgnts,bsge->btgne", probs, values, dt)
        return matmul("btgne,gned->btd", out, w_o, dt, _F32), rows


class MoE(experts.RoutedMoE):
    """This source's expert layer: ``experts.RoutedMoE`` with no shared
    expert, 1e-6 in the weights' normalisation, and overflow chunks of
    ``CHUNK_ROWS`` (read at every call)."""
    topk_eps: float = 1e-6
    shared: bool = False
    out_init: Any = _INIT_OUT

    @property
    def chunk_rows(self) -> int:
        return CHUNK_ROWS


class Layer(nn.Module):
    """One pre-norm layer: ``x += Op(norm(x)); x += FFN(norm(x))``; ``kind``
    names the operator, ``stored`` is its part of the state row. Returns the
    stream, the part as the window leaves it, and the router's counters
    (None for a dense layer)."""
    core: CoreConfig
    dtype: Any
    kind: str
    dense: bool
    window_stats: bool

    @nn.compact
    def __call__(self, x, stored):
        c, dt = self.core, self.dtype
        d = x.shape[-1]
        w_op = self.param("operator_norm", nn.initializers.ones, (d,))
        w_ffn = self.param("ffn_norm", nn.initializers.ones, (d,))
        if self.kind == "conv":
            with jax.named_scope("short_conv"):
                out, new = ShortConv(c, dt, name="conv")(
                    rms_norm(x, w_op, c.rms_norm_eps), stored)
                x = x + out
        else:
            with jax.named_scope("gqa_attn"):
                out, rows = GroupedQueryAttention(c, dt, name="self_attn")(
                    rms_norm(x, w_op, c.rms_norm_eps), stored)
                new = jnp.concatenate([stored.astype(_F32), rows],
                                      axis=1)[:, -c.memory_len:]
                x = x + out
        if self.dense:
            with jax.named_scope("dense_mlp"):
                b, t, _ = x.shape
                out = SwiGLU(c.intermediate_size, dt, _INIT_OUT,
                             c.rms_norm_eps, name="mlp")(
                                 x.reshape(b * t, d), w_ffn)
                out, stats = out.reshape(b, t, d), None
        else:
            out, stats = MoE(c, dt, self.window_stats, name="mlp")(x, w_ffn)
        return x + out, new, stats


class ConvAttnMoeStack(nn.Module):
    """Input projection, the layers (each under ``jax.checkpoint``: the
    backward pass recomputes a layer's activations, which changes no value),
    the final norm; the state row in, unpacked by ``state_layout``, and out,
    packed the same way. The expert layers' routing counters are sown into
    the ``moe`` collection (``NetworkApply.apply_learner`` reads them)."""
    core: CoreConfig
    dtype: Any
    window_stats: bool

    @nn.compact
    def __call__(self, x_seq, state):
        c = self.core
        b = x_seq.shape[0]
        flat = state.astype(_F32).reshape(b, -1)
        w_in = self.param("input_proj", nn.initializers.lecun_normal(),
                          (x_seq.shape[-1], c.hidden_size))
        # the stream starts at unit size, as after an embedding's norm
        x = Norm(c.rms_norm_eps, name="input_norm")(
            matmul("btd,de->bte", x_seq, w_in, self.dtype, _F32))
        new_parts, stats = [], []
        for part in state_layout(c):
            stored = flat[:, part.offset:part.offset + part.size].reshape(
                (b,) + part.shape)
            x, new, s = nn.remat(Layer)(
                c, self.dtype, kind=part.kind,
                dense=part.layer < c.first_k_dense_replace,
                window_stats=self.window_stats,
                name=f"layers_{part.layer}")(x, stored)
            new_parts.append(new.reshape(b, -1))
            if s is not None:
                stats.append(s)
        if stats:
            self.sow("moe", "counters", jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *stats))
        y = Norm(c.rms_norm_eps, name="norm")(x).astype(self.dtype)
        return y, jnp.concatenate(new_parts, axis=1).reshape(b, 2, -1)


@dataclasses.dataclass(frozen=True)
class ConvAttnMoeCore:
    core: CoreConfig
    dtype: Any
    scope = SCOPE

    @property
    def state_half(self) -> int:
        # every part is a multiple of a head's width, which is even
        return sum(part.size for part in state_layout(self.core)) // 2

    @property
    def out_dim(self) -> int:
        return self.core.hidden_size

    @property
    def routes_experts(self) -> bool:
        return self.core.num_hidden_layers > self.core.first_k_dense_replace

    def state_parts(self) -> List[Tuple[str, int, int]]:
        names = {"conv": "conv_state", "full_attention": "key_value_window"}
        sizes = {kind: [p.size for p in state_layout(self.core)
                        if p.kind == kind] for kind in names}
        return [(names[kind], len(found), sum(found))
                for kind, found in sizes.items() if found]

    def init_state(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch, 2, self.state_half), _F32)

    def unroll(self, x_seq: jnp.ndarray, state: jnp.ndarray,
               window_stats: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return ConvAttnMoeStack(self.core, self.dtype, window_stats,
                                name=self.scope)(x_seq, state)
