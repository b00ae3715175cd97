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
leaves out what the others would add. The router, the sort of the pairs, the
chunk walk through the grouped products and the way back are ``experts.py``'s
(the cores that route share them); this file fixes what this source fixes:
the 1e-20 in the weights' normalisation, the shared expert, the residual
initialiser at the published depth of 27, and ``CHUNK_ROWS``.

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

The router reads what varies between positions: its input is h less its
mean m over positions (``experts.py`` says why and how acting gets m).

Departures from the source, all written in the benchmark's configuration
file too: the router's centred input (above); the correction bias b is held
among the parameters but only the choice reads it, so no gradient reaches
it, and no balance rule updates it (zeros at init); no ``seq_aux`` loss; no
multi-token prediction; the input is the torso's latent and the one-hot
last action through a bias-free projection and an RMSNorm where the token
embedding stood.
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.config import CoreConfig
from r2d2_tpu.models.cores import experts
from r2d2_tpu.models.cores.experts import (  # noqa: F401  (re-exported)
    INIT, SCOPE, Norm, SwiGLU, matmul, moe_counters, rms_norm, rope,
    store_router_means)

# the projections that write into the residual stream (o_proj, every
# down_proj) are drawn for the published depth
_INIT_OUT = experts.residual_init(27)
_F32 = jnp.float32


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
        w_q = self.param("q_proj", INIT, (d, heads, dn + dr))
        w_kva = self.param("kv_a_proj_with_mqa", INIT, (d, dc + dr))
        w_kvb = self.param("kv_b_proj", INIT, (dc, heads, dn + dv))
        w_o = self.param("o_proj", _INIT_OUT, (heads, dv, d))

        q = matmul("btd,dhe->bthe", h, w_q, dt)
        kva = matmul("btd,de->bte", h, w_kva, dt, _F32)
        latent = Norm(c.rms_norm_eps, name="kv_a_layernorm")(kva[..., :dc])
        rows = jnp.concatenate([latent, kva[..., dc:]], axis=-1)  # (B,T,dc+dr)

        mem = jax.lax.stop_gradient(mem.astype(_F32))             # (B,M,dc+dr)
        keys = jnp.concatenate([mem, rows], axis=1)               # (B,M+T,.)
        key_pos = jnp.arange(-m, t)
        kvb = matmul("bsc,che->bshe", keys[..., :dc], w_kvb, dt)
        k_rot = rope(keys[..., dc:], key_pos, c.rope_theta)       # (B,S,dr)
        q_rot = rope(q[..., dn:], jnp.arange(t), c.rope_theta)    # (B,T,H,dr)
        scores = (matmul("bthe,bshe->bhts", q[..., :dn], kvb[..., :dn], dt,
                         _F32)
                  + matmul("bthe,bse->bhts", q_rot, k_rot, dt, _F32))
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
        values = matmul("bhts,bshe->bthe", probs, kvb[..., dn:], dt)
        return matmul("bthe,hed->btd", values, w_o, dt, _F32), rows


def route(scores, bias, core: CoreConfig):
    """``experts.route`` with this source's sizes and its 1e-20."""
    return experts.route(scores, bias, core.num_experts_per_tok,
                         core.routed_scaling_factor, 1e-20)


# Rows of an overflow chunk: what ``experts.held_experts_ffn`` takes at a time
# of the sorted pairs past its first chunk (which holds the pairs a layer
# expects, from the shapes: 6,656 rows for the benchmark's 6,000). What an
# overflow chunk costs is mostly not its rows (the weights' gradient summed,
# the sums streamed, grouped products that small chunks do not fill): one
# layer forward and backward, 7.4 ms with none over, reads 10.6 | 11.7 | 11.6
# with 6,900 pairs at chunks of 512 | 1,024 | 2,048 rows and 13.8 | 13.1 |
# 10.8 with 8,000 pairs on one held expert (the parent's walk: 17.5 and
# 20.2; my chip runs, PR 34). So a router just over its expectation pays
# about the same whatever the size and a skewed one pays fewer of them.
# Read at every call.
CHUNK_ROWS = 1024


class MoE(experts.RoutedMoE):
    """This source's expert layer: ``experts.RoutedMoE`` with the shared
    expert, 1e-20 in the weights' normalisation, and overflow chunks of
    ``CHUNK_ROWS`` (read at every call)."""
    topk_eps: float = 1e-20
    shared: bool = True
    out_init: Any = _INIT_OUT

    @property
    def chunk_rows(self) -> int:
        return CHUNK_ROWS


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
                out = SwiGLU(c.intermediate_size, dt, _INIT_OUT,
                             c.rms_norm_eps, name="mlp")(x.reshape(b * t, d), w_post)
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
        x = Norm(c.rms_norm_eps, name="input_norm")(
            matmul("btd,de->bte", x_seq, w_in, self.dtype, _F32))
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
        y = Norm(c.rms_norm_eps, name="norm")(x).astype(self.dtype)
        return y, jnp.stack(new_mem, axis=1).reshape(b, 2, -1)


@dataclasses.dataclass(frozen=True)
class MlaMoeCore:
    core: CoreConfig
    dtype: Any
    scope = SCOPE

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

    @property
    def routes_experts(self) -> bool:
        return self.core.num_hidden_layers > self.core.first_k_dense_replace

    def state_parts(self):
        return [("latent_cache", self.core.num_hidden_layers,
                 2 * self.state_half)]

    def init_state(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch, 2, self.state_half), _F32)

    def unroll(self, x_seq: jnp.ndarray, state: jnp.ndarray,
               window_stats: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return MlaMoeStack(self.core, self.dtype, window_stats,
                           name=self.scope)(x_seq, state)

