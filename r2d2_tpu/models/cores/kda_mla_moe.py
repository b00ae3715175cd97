"""A stack of Kimi-Linear-form layers as the agent's memory core: each layer
Kimi Delta Attention (KDA, a gated delta rule with a decay for each key
channel) or the latent attention of ``mla_moe.py`` in its NoPE form, by
``layer_types``; then a dense SwiGLU (the first ``first_k_dense_replace``
layers) or a mixture of experts of which this chip holds ``experts_held`` of
``n_routed_experts``. Kimi-Linear-48B-A3B's layers
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/
config.json, ``model_type: kimi_linear``), KDA as the Kimi Linear technical
report (arXiv:2510.26692) and the ``flash-linear-attention`` project's KDA
layer define it.

Equations, per layer on the residual stream x (``hidden_size``), pre-norm
RMSNorm (``rms_norm_eps``), no biases but the output gate's, and a final
RMSNorm before the head::

    x += Mixer(RMSNorm(x));   x += FFN(RMSNorm(x))

    KDA(h): H = kda_num_heads heads of e = kda_head_dim
      q~, k~, v~ = W_q h, W_k h, W_v h                  (d -> H e each)
      conv(.) = causal depthwise convolution of short_conv_kernel_size
                taps, no bias, over [stored last inputs | window]
      q = l2norm(silu(conv(q~))) / sqrt(e),  k = l2norm(silu(conv(k~))),
      v = silu(conv(v~))                                (per head)
      g_t    = -exp(A_log[head]) softplus(W_fb W_fa h_t + dt_bias)
                                                        (rank e)
      beta_t = sigmoid(W_b h_t)                         (one per head)
      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                                   (e x e, float32)
      out = W_o concat_heads(RMSNorm_e(o_t) sigmoid(W_gb W_ga h_t + b_g))
    MLA(h): ``mla_moe.LatentAttention`` with ``mla_use_nope`` (no rotation)
      over [stored latents | causal window], in blocks of ``q_chunk_size``
      queries
    FFN: ``mla_moe``'s dense SwiGLU and sigmoid-routed experts with the
      shared expert (the same forms: sigmoid scores, the correction bias in
      the choice, weights normalised with 1e-20 and scaled by
      ``routed_scaling_factor``)

The state row: one float32 ``(2, state_half)`` row a sequence, the layers'
parts in layer order: a KDA layer's S at the window's last position (H, e,
e) and the last ``short_conv_kernel_size - 1`` pre-convolution inputs of
[q~ | k~ | v~]; an MLA layer's ``memory_len`` latent positions as
``mla_moe.py`` stores them. A window reads its part with no gradient into it
(R2D2's stored state); at T = 1 (the actor's step) the recurrence takes one
step and the convolution's inputs and the latent cache shift by one.

Precision: matrix products take ``dtype`` operands (bf16 on a TPU) and
accumulate in float32, q, k and v enter the scan in ``dtype``; the scan
(``ops/kda.py``: a Pallas kernel forward and backward on a TPU, one step a
position elsewhere), the decays, the convolution, the norms, the gates and
the residual stream are float32.

Scopes (the device trace's rows), inside ``mem_core``: ``kda_proj`` (the
six projections and the convolutions), ``kda_scan`` (the scan, forward and
backward), ``kda_out`` (the gated norm and W_o), ``mla_attn``,
``dense_mlp`` and ``experts.py``'s ``moe_*``.

Departures from the source, written in benchmarks/configs/kimi-linear-
core.json too: the low-rank width of the decay's and the output gate's
projections (``kda_head_dim``), the output gate's bias, the initialisation
of A_log, dt_bias and the convolutions (the flash-linear-attention layer's);
the router's centred input and its correction bias held at zero
(experts.py); the input projection and the dueling head where the
embedding and the vocabulary stood.
"""

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import CoreConfig
from r2d2_tpu.models.cores import mla_moe
from r2d2_tpu.models.cores.experts import (INIT, SCOPE, Norm, SwiGLU, matmul,
                                           rms_norm)
from r2d2_tpu.ops import kda

# the projections that write into the residual stream are drawn for the
# published depth, as mla_moe.py's (27 layers there and here)
_INIT_OUT = mla_moe._INIT_OUT
_F32 = jnp.float32
# a channel that decays by more than e^-FORGET over one chunk forgets
# within it (the record's ``forgets_in_chunk``)
FORGET = 20.0


def _conv_init(key, shape, dtype=_F32):
    """PyTorch's Conv1d default for a depthwise convolution: uniform in
    +-1/sqrt(taps)."""
    bound = 1.0 / math.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=_F32):
    """log of uniform(1, 16), one a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=_F32):
    """softplus^-1 of dt, dt log-uniform in [0.001, 0.1] (at least 1e-4)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def layout(core: CoreConfig) -> List[Tuple[str, int]]:
    """Per layer in order, (its kind, the floats of its part of the state
    row)."""
    width = core.kda_num_heads * core.kda_head_dim
    parts = {"kda": core.kda_num_heads * core.kda_head_dim ** 2
             + (core.short_conv_kernel_size - 1) * 3 * width,
             "full_attention": core.memory_len
             * (core.kv_lora_rank + core.qk_rope_head_dim)}
    return [(kind, parts[kind]) for kind in core.layer_types]


def _short_conv(h, tail, w_proj, taps_w, core: CoreConfig, dtype,
                normed: bool, scale: float = 1.0):
    """One of q, k, v: the projection of the normed stream ``h`` (B, T, d)
    in ``dtype``, its causal depthwise convolution over [``tail`` |
    window] (``tail`` (B, taps - 1, H e), its last inputs), silu, and where
    ``normed`` the L2 norm of each head, times ``scale``. Returns it (B, H,
    T, e) in ``dtype`` and the convolution's last ``taps - 1`` inputs,
    float32 holding ``dtype``'s values."""
    heads, e = core.kda_num_heads, core.kda_head_dim
    b, t = h.shape[0], h.shape[1]
    both = jnp.concatenate(
        [jax.lax.stop_gradient(tail).astype(dtype),
         matmul("btd,df->btf", h, w_proj, dtype)], axis=1)
    conv = sum(taps_w[:, j] * both[:, j:j + t].astype(_F32)
               for j in range(taps_w.shape[-1]))
    x = jax.nn.silu(conv).reshape(b, t, heads, e)
    if normed:
        x = x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
                 * scale)
    return (jnp.moveaxis(x, 2, 1).astype(dtype),
            both[:, t:].astype(_F32))


def _gates(h, w, core: CoreConfig, dtype):
    """The log-decays g (B, H, T, e) and beta (B, H, T), float32, of the
    normed stream ``h``."""
    heads, e = core.kda_num_heads, core.kda_head_dim
    b, t = h.shape[0], h.shape[1]
    low = matmul("btd,dr->btr", h, w["f_a_proj"], dtype)
    g = matmul("btr,rf->btf", low, w["f_b_proj"], dtype, _F32)
    g = (-jnp.exp(w["A_log"])[:, None]
         * jax.nn.softplus(g + w["dt_bias"]).reshape(b, t, heads, e))
    beta = jax.nn.sigmoid(matmul("btd,dh->bth", h, w["b_proj"], dtype, _F32))
    return jnp.moveaxis(g, 2, 1), jnp.moveaxis(beta, 2, 1)


def _gated_out(h, o, w, core: CoreConfig, dtype):
    """The scan's output ``o`` (B, H, T, e) through the gated norm and W_o:
    (B, T, d) float32."""
    low = matmul("btd,dr->btr", h, w["g_a_proj"], dtype)
    gate = matmul("btr,rf->btf", low, w["g_b_proj"], dtype, _F32) \
        + w["g_b_bias"]
    o = rms_norm(jnp.moveaxis(o, 1, 2), w["o_norm"], core.rms_norm_eps)
    o = o * jax.nn.sigmoid(gate.reshape(o.shape))
    return matmul("bthe,hed->btd", o, w["o_proj"], dtype, _F32)


class DeltaAttention(nn.Module):
    """KDA over [stored | window]: ``h`` (B, T, d) the normed stream,
    ``state`` (B, H, e, e) and ``tail`` (B, taps - 1, 3 H e) the stored
    part. Returns the output (B, T, d) float32, the new state and tail, and,
    in the learner's call (``learner``), the layer's counters."""
    core: CoreConfig
    dtype: Any
    learner: bool

    @nn.compact
    def __call__(self, h, state, tail):
        c, dt = self.core, self.dtype
        d, heads, e = c.hidden_size, c.kda_num_heads, c.kda_head_dim
        width, taps = heads * e, c.short_conv_kernel_size
        taps_of = {n: self.param(f"{n}_conv1d", _conv_init, (width, taps))
                   for n in "qkv"}
        gates = {"A_log": self.param("A_log", _a_log_init, (heads,)),
                 "dt_bias": self.param("dt_bias", _dt_bias_init, (width,)),
                 "f_a_proj": self.param("f_a_proj", INIT, (d, e)),
                 "f_b_proj": self.param("f_b_proj", INIT, (e, width)),
                 "b_proj": self.param("b_proj", INIT, (d, heads))}
        # the scan's inputs, each with its own activations recomputed in
        # the backward pass: the layer's backward holds the scan's inputs,
        # and one of the convolutions' (B, T, H e) float32 activations at
        # a time
        with jax.named_scope("kda_proj"):
            found = [jax.checkpoint(functools.partial(
                _short_conv, core=c, dtype=dt, normed=n != "v",
                scale=e ** -0.5 if n == "q" else 1.0))(
                    h, tail[..., i * width:(i + 1) * width],
                    self.param(f"{n}_proj", INIT, (d, width)), taps_of[n])
                for i, n in enumerate("qkv")]
            (q, q_tail), (k, k_tail), (v, v_tail) = found
            g, beta = jax.checkpoint(functools.partial(
                _gates, core=c, dtype=dt))(h, gates)
            new_tail = jnp.concatenate([q_tail, k_tail, v_tail], axis=-1)
        with jax.named_scope("kda_scan"):
            o, s = kda.kda_scan(
                q, k, v, g, beta, jax.lax.stop_gradient(state.astype(_F32)),
                c.kda_chunk_size)
        with jax.named_scope("kda_out"):
            out = jax.checkpoint(functools.partial(
                _gated_out, core=c, dtype=dt))(h, o, {
                    "g_a_proj": self.param("g_a_proj", INIT, (d, e)),
                    "g_b_proj": self.param("g_b_proj", INIT, (e, width)),
                    "g_b_bias": self.param("g_b_bias", nn.initializers.zeros,
                                           (width,)),
                    "o_norm": self.param("o_norm", nn.initializers.ones,
                                         (e,)),
                    "o_proj": self.param("o_proj", _INIT_OUT,
                                         (heads, e, d))})
        if not self.learner:
            return out, s, new_tail, None
        g = jax.lax.stop_gradient(g)
        stats = {
            # per position (B, T): the learner takes their means over the
            # learning positions
            "log_decay": jnp.mean(g, axis=(1, 3)),
            "forgets_in_chunk": jnp.mean(
                (g * c.kda_chunk_size < -FORGET).astype(_F32), axis=(1, 3)),
            "beta": jnp.mean(jax.lax.stop_gradient(beta), axis=1),
            # per window: ||S||_F of each head at its end, the mean
            "state_norm": jnp.mean(jnp.sqrt(jnp.sum(
                jax.lax.stop_gradient(s) ** 2, axis=(2, 3)))),
        }
        return out, s, new_tail, stats


class Layer(nn.Module):
    """One pre-norm layer: ``x += Mixer(norm(x)); x += FFN(norm(x))``;
    ``stored`` is its part of the state row (B, floats). Returns the
    stream, the part as the window leaves it, the router's counters (None
    for a dense layer) and KDA's (None but in the learner's call of a KDA
    layer)."""
    core: CoreConfig
    dtype: Any
    kind: str
    dense: bool
    window_stats: bool

    @nn.compact
    def __call__(self, x, stored):
        c, dt = self.core, self.dtype
        b, t, d = x.shape
        w_in = self.param("input_layernorm", nn.initializers.ones, (d,))
        w_post = self.param("post_attention_layernorm", nn.initializers.ones,
                            (d,))
        counters = None
        if self.kind == "kda":
            heads, e = c.kda_num_heads, c.kda_head_dim
            at = heads * e * e
            out, s, tail, counters = DeltaAttention(
                c, dt, self.window_stats, name="self_attn")(
                    rms_norm(x, w_in, c.rms_norm_eps),
                    stored[:, :at].reshape(b, heads, e, e),
                    stored[:, at:].reshape(
                        b, c.short_conv_kernel_size - 1, -1))
            new = jnp.concatenate([s.reshape(b, -1), tail.reshape(b, -1)],
                                  axis=1)
        else:
            mem = stored.reshape(b, c.memory_len, -1)
            with jax.named_scope("mla_attn"):
                out, rows = mla_moe.LatentAttention(c, dt, name="self_attn")(
                    x, w_in, mem)
            new = jnp.concatenate([mem.astype(_F32), rows],
                                  axis=1)[:, -c.memory_len:].reshape(b, -1)
        x = x + out
        if self.dense:
            with jax.named_scope("dense_mlp"):
                out = SwiGLU(c.intermediate_size, dt, _INIT_OUT,
                             c.rms_norm_eps, name="mlp")(
                                 x.reshape(b * t, d), w_post)
                out, stats = out.reshape(b, t, d), None
        else:
            out, stats = mla_moe.MoE(c, dt, self.window_stats, name="mlp")(
                x, w_post)
        return x + out, new, stats, counters


class KdaMlaMoeStack(nn.Module):
    """Input projection, the layers (each under ``jax.checkpoint``: the
    backward pass recomputes a layer's activations, the scan's forward
    among them), the final norm; the state row in and out, a layer's part
    after another's (``layout``). The expert layers' routing counters are
    sown into the ``moe`` collection, the KDA layers' counters into
    ``core`` (``NetworkApply.apply_learner`` reads both)."""
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
        x = Norm(c.rms_norm_eps, name="input_norm")(
            matmul("btd,de->bte", x_seq, w_in, self.dtype, _F32))
        new_parts, stats, counters = [], [], []
        at = 0
        for i, (kind, floats) in enumerate(layout(c)):
            x, new, s, k = nn.remat(Layer)(
                c, self.dtype, kind, dense=i < c.first_k_dense_replace,
                window_stats=self.window_stats, name=f"layers_{i}")(
                    x, flat[:, at:at + floats])
            at += floats
            new_parts.append(new)
            if s is not None:
                stats.append(s)
            if k is not None:
                counters.append(k)
        stack = lambda found: jax.tree_util.tree_map(  # noqa: E731
            lambda *xs: jnp.stack(xs), *found)
        if stats:
            self.sow("moe", "counters", stack(stats))
        if counters:
            self.sow("core", "counters", stack(counters))
        y = Norm(c.rms_norm_eps, name="norm")(x).astype(self.dtype)
        return y, jnp.concatenate(new_parts, axis=1).reshape(b, 2, -1)


@dataclasses.dataclass(frozen=True)
class KdaMlaMoeCore:
    core: CoreConfig
    dtype: Any
    scope = SCOPE

    def __post_init__(self):
        if sum(floats for _, floats in layout(self.core)) % 2:
            raise ValueError("network.core: the state row does not pack "
                             "into two equal halves")

    @property
    def state_half(self) -> int:
        return sum(floats for _, floats in layout(self.core)) // 2

    @property
    def out_dim(self) -> int:
        return self.core.hidden_size

    @property
    def routes_experts(self) -> bool:
        return self.core.num_hidden_layers > self.core.first_k_dense_replace

    def state_parts(self) -> List[Tuple[str, int, int]]:
        c = self.core
        kda_layers = c.layer_types.count("kda")
        mla_layers = c.layer_types.count("full_attention")
        width = c.kda_num_heads * c.kda_head_dim
        return [("kda_state", kda_layers,
                 kda_layers * c.kda_num_heads * c.kda_head_dim ** 2),
                ("conv_tail", kda_layers,
                 kda_layers * (c.short_conv_kernel_size - 1) * 3 * width),
                ("latent_cache", mla_layers, mla_layers * c.memory_len
                 * (c.kv_lora_rank + c.qk_rope_head_dim))]

    def init_state(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch, 2, self.state_half), _F32)

    def unroll(self, x_seq: jnp.ndarray, state: jnp.ndarray,
               window_stats: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return KdaMlaMoeStack(self.core, self.dtype, window_stats,
                              name=self.scope)(x_seq, state)

    def record_block(self, batch: int, window: int) -> Dict[str, Any]:
        """KDA's sizes for the record's one-shot ``core`` block: its
        layers, heads, chunk, the chunks a window of ``window`` positions
        takes, and a KDA layer's bytes of the state row."""
        c = self.core
        floats = dict(layout(c))["kda"] if "kda" in c.layer_types else 0
        return {"kda": {"layers": c.layer_types.count("kda"),
                        "heads": c.kda_num_heads,
                        "head_dim": c.kda_head_dim,
                        "chunk": c.kda_chunk_size,
                        "chunks_per_window": -(-window // c.kda_chunk_size),
                        "layer_row_bytes": 4 * floats}}

    def summarise(self, sums: Dict[str, np.ndarray], steps: int
                  ) -> Dict[str, Any]:
        """The record's ``kda`` block from the sums, over ``steps`` train
        steps, of the counters ``DeltaAttention`` sows, per KDA layer (each
        a mean over the learning positions, or over the heads and windows
        of a step, then over the steps): the mean log-decay of a channel at
        a position, the share of channels that decay by more than e^-20
        over one chunk at that rate, the mean beta, and the mean ||S||_F of
        a head at the window's end."""
        layers = [{name: float(sums[name][i] / steps)
                   for name in ("log_decay", "forgets_in_chunk", "beta",
                                "state_norm")}
                  for i in range(len(sums["beta"]))]
        return {"kda": {"steps": steps, "layers": layers}}
