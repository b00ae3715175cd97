"""A stack of Qwen3-MoE-form layers whose grouped-query attention reads, for
each query, the keys a learned indexer chooses (DeepSeek-V3.2's sparse
attention), as the agent's memory core: Keye-VL-2.0-30B-A3B's language model
layers (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/
config.json, ``model_type: KeyeVL2``, its ``sa_config``), of which this chip
holds ``experts_held`` of each layer's ``n_routed_experts``.

Equations, per layer on the residual stream x (``hidden_size``), pre-norm
RMSNorm (``rms_norm_eps``), no biases, and a final RMSNorm before the head::

    x += Attn(RMSNorm(x));   x += FFN(RMSNorm(x))

    Attn(h): q = W_q h -> H heads x e; k = W_k h, v = W_v h -> G heads x e
             (e = head_dim); q, k <- RMSNorm_e per head (QK-norm); q, k
             rotated (rotate_half, rope_theta; M-RoPE with one position id
             for its three sections is this 1-D rotation: an agent's step
             stream has no image grid)
      indexer, reading stop_gradient(h):
             qI = W_qI h -> J heads x c; kI = LayerNorm(W_kI h) -> 1 head x c;
             w = W_w h -> J, times J^-1/2; qI, kI rotated as q, k
             I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) c^-1/2, s visible
      S_t = the index_topk visible keys of largest I[t,.] (every visible key
            where a query sees no more than index_topk; ties to the earlier
            key, as lax.top_k breaks them)
      out_t = W_o concat_h softmax_{s in S_t}(q[t,h] . k[s,h//n] / sqrt(e))
              v[s,h//n]                         (n = H / G)

    FFN: the first ``first_k_dense_replace`` layers W_2(silu(W_1 h) * W_3 h),
    ``intermediate_size`` wide; the others ``experts.py``'s routed experts in
    the configuration's ``scoring_func`` (Keye: softmax over all routed
    experts, the top k renormalised over the chosen, no bias, no shared
    expert).

    The indexer's loss, per window position t (DeepSeek-V3.2's):
      P_t = (1/H) sum_h softmax_{s in S_t}(...) of the attention above, a
            distribution over S_t held as a constant
      KL_t = KL(P_t || softmax_{s in S_t}(I[t,.]))
    sown per position as the core's loss (the ``core`` collection, key
    ``loss``); the learner averages it over its learning positions and adds
    it to the TD loss (train_step.py). The
    indexer's weights learn from it alone, and nothing else does: the
    indexer reads stop_gradient(h), and the attention reads its choice, not
    its scores.

The state row: one float32 ``(2, state_half)`` row a sequence, each layer's
``memory_len`` positions of (k after its norm, before its rotation | v | kI
after its norm, before its rotation), oldest first, layer after layer. A
slot whose values are all zero is empty and not visible. Slot j stands at
position j - memory_len, window step t at t; the keys are laid out as
[zeros | stored slots | window], the zeros filling the keys to whole blocks
of ``kv_chunk_size`` and never visible. A window reads the stored slots as a
prefix with no gradient into them (R2D2's stored state); at T = 1 (the
actor's step) the window of stored positions shifts by one.

Precision: matrix products, the attention's and the indexer's among them,
take ``dtype`` operands (bf16 on a TPU) and accumulate in float32; the
residual stream, the norms, the rotation, the indexer's scores and
threshold, the softmaxes and the loss are float32.

Scopes (the device trace's rows), inside ``mem_core``: ``gqa_attn`` (the
projections, norms and rotation), ``dsa_indexer`` (the indexer's
projections and scores, and their backward), ``dsa_select`` (the threshold
and the masks of what is chosen), ``dsa_attn`` (attention over the chosen
keys, forward and backward), ``dsa_kl`` (the heads' probabilities and the
loss), ``dense_mlp``, and ``experts.py``'s ``moe_*``. The attention and its
indexer run as ``ops/sparse_attention.py``'s Pallas kernels on a TPU.

Departures from the source, written in benchmarks/configs/keye-core.json
too: the indexer reads h (DeepSeek-V3.2 computes its queries from the query
latent, which Keye has none of), rotates its whole heads, normalises kI by
a LayerNorm and computes in bf16 (FP8 in DeepSeek-V3.2); the router's
centred input (experts.py); the input projection and the dueling head where
the embedding and the vocabulary stood, the torso where the vision tower
stood.
"""

import dataclasses
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from r2d2_tpu.config import CoreConfig
from r2d2_tpu.models.cores import experts
from r2d2_tpu.models.cores.experts import (INIT, SCOPE, Norm, SwiGLU, matmul,
                                           rms_norm, rope)
from r2d2_tpu.ops import sparse_attention as sparse

# the projections that write into the residual stream are drawn for the
# published depth
_INIT_OUT = experts.residual_init(48)
_F32 = jnp.float32
# Rows of an overflow chunk of the held experts' walk: the other cores'
CHUNK_ROWS = 1024
# What a layer's remat keeps for its backward, beside the layer's inputs: the
# attention's output and log-sum-exp (named in ``sparse_attention``'s
# forward) and each query's threshold and cut. The backward then runs
# neither the attention's forward kernel nor the threshold's bisection
# again; the rest of the layer it recomputes.
THRESHOLD, CUT = "dsa_threshold", "dsa_cut"
KEPT = (sparse.OUT, sparse.LSE, THRESHOLD, CUT)
_KEEP = jax.checkpoint_policies.save_only_these_names(*KEPT)


def row_width(core: CoreConfig) -> int:
    """Floats a stored position holds in one layer: k | v | kI."""
    return 2 * core.num_key_value_heads * core.head_dim + core.index_head_dim


def key_layout(core: CoreConfig, t: int) -> Tuple[int, int]:
    """(keys a window of ``t`` steps attends over, the zeros in front of
    the stored slots): the stored slots and the window, in whole blocks of
    ``kv_chunk_size``."""
    seen = core.memory_len + t
    keys = -(-seen // core.kv_chunk_size) * core.kv_chunk_size
    return keys, keys - seen


def _layer_norm(x, weight, bias, eps: float):
    x = x.astype(_F32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


class Indexer(nn.Module):
    """The lightning indexer's projections of the (stopped) stream ``h``
    (B, T, d): its queries (B, T, J, c) and weights (B, T, J) float32 (the
    scales J^-1/2 and c^-1/2 folded into the weights), and its key (B, T, c)
    after its LayerNorm."""
    core: CoreConfig
    dtype: Any

    @nn.compact
    def __call__(self, h):
        c, dt = self.core, self.dtype
        d, j, width = c.hidden_size, c.index_n_heads, c.index_head_dim
        w_q = self.param("wq", INIT, (d, j, width))
        w_k = self.param("wk", INIT, (d, width))
        w_w = self.param("weights_proj", INIT, (d, j))
        k_norm_w = self.param("k_norm_weight", nn.initializers.ones,
                              (width,))
        k_norm_b = self.param("k_norm_bias", nn.initializers.zeros, (width,))
        q = matmul("btd,dje->btje", h, w_q, dt, _F32)
        k = _layer_norm(matmul("btd,de->bte", h, w_k, dt, _F32), k_norm_w,
                        k_norm_b, c.rms_norm_eps)
        w = matmul("btd,dj->btj", h, w_w, dt, _F32) * (j * width) ** -0.5
        return q, w, k


def _counters(chosen, visible, over, key_pos, t: int, topk: int,
              bq: int, bk: int):
    """The layer's ``dsa`` counters of one window from its masks (B, T, S):
    queries that chose (saw more than ``topk`` keys), pairs chosen, chosen
    keys more than ``topk`` positions back, and the (query, key) blocks of
    ``bq`` x ``bk`` that hold a visible pair but no chosen one (the blocks
    the kernel skips beyond the causal ones)."""
    b, s = chosen.shape[0], chosen.shape[2]
    back = jnp.arange(t)[:, None] - key_pos[None, :] > topk
    bq, bk = min(bq, t), min(bk, s)
    if t % bq or s % bk:
        skipped = jnp.zeros((), jnp.int32)
    else:
        has_chosen = sparse.blocks_live(jnp.where(chosen, 0.0, -1.0), bq, bk)
        has_visible = sparse.blocks_live(jnp.where(visible, 0.0, -1.0), bq,
                                         bk)
        skipped = jnp.sum(has_visible & ~has_chosen, dtype=jnp.int32)
    return {"queries": jnp.asarray(b * t, jnp.int32),
            "selecting": jnp.sum(over, dtype=jnp.int32),
            "pairs": jnp.sum(chosen, dtype=jnp.int32),
            "far": jnp.sum(chosen & back[None], dtype=jnp.int32),
            "blocks_skipped": skipped}


class SparseAttention(nn.Module):
    """Grouped-query attention over the keys the indexer chooses, over
    [zeros | stored slots | window]: ``h`` (B, T, d) the normed stream,
    ``stored`` (B, M, row_width) the stored slots. Returns the attention
    output (B, T, d) float32, the window's own rows (B, T, row_width)
    float32, and, for the learner's call (``learner``), the layer's
    counters with ``loss`` (B, T), the indexer's loss at each position."""
    core: CoreConfig
    dtype: Any
    learner: bool

    @nn.compact
    def __call__(self, h, stored):
        c, dt = self.core, self.dtype
        d, heads, groups, e = (c.hidden_size, c.num_attention_heads,
                               c.num_key_value_heads, c.head_dim)
        n, m, topk = heads // groups, c.memory_len, c.index_topk
        b, t = h.shape[0], h.shape[1]
        s, pad = key_layout(c, t)
        bq, bk = min(c.q_chunk_size, t), min(c.kv_chunk_size, s)
        w_q = self.param("q_proj", INIT, (d, groups, n, e))
        w_k = self.param("k_proj", INIT, (d, groups, e))
        w_v = self.param("v_proj", INIT, (d, groups, e))
        w_o = self.param("o_proj", _INIT_OUT, (groups, n, e, d))

        with jax.named_scope("gqa_attn"):
            q = Norm(c.rms_norm_eps, name="q_norm")(
                matmul("btd,dgne->btgne", h, w_q, dt, _F32))
            k = Norm(c.rms_norm_eps, name="k_norm")(
                matmul("btd,dge->btge", h, w_k, dt, _F32))
            v = matmul("btd,dge->btge", h, w_v, dt, _F32)
        with jax.named_scope("dsa_indexer"):
            q_idx, w_idx, k_idx = Indexer(c, dt, name="indexer")(
                jax.lax.stop_gradient(h))
        rows = jnp.concatenate([k.reshape(b, t, -1), v.reshape(b, t, -1),
                                k_idx], axis=-1)

        with jax.named_scope("gqa_attn"):
            stored = jax.lax.stop_gradient(stored.astype(_F32))
            both = jnp.concatenate(
                [jnp.zeros((b, pad, stored.shape[-1]), _F32), stored, rows],
                axis=1)                                        # (B, S, .)
            key_pos = jnp.arange(s) - pad - m
            keys = rope(both[..., :groups * e].reshape(b, s, groups, e),
                        key_pos, c.rope_theta)
            values = both[..., groups * e:2 * groups * e].reshape(
                b, s, groups, e)
            q_rot = rope(q.reshape(b, t, heads, e), jnp.arange(t),
                         c.rope_theta)
            # heads-major for the kernels: query head g * n + i reads g
            q_rot = q_rot.transpose(0, 2, 1, 3).astype(dt)
            keys = keys.transpose(0, 2, 1, 3).astype(dt)
            values = values.transpose(0, 2, 1, 3).astype(dt)
            filled = jnp.concatenate(
                [jnp.zeros((b, pad), bool), jnp.any(stored != 0, axis=-1),
                 jnp.ones((b, t), bool)], axis=1)              # (B, S)
            visible = (filled[:, None, :]
                       & (key_pos[None, :] <= jnp.arange(t)[:, None])[None])

        # the indexer's scores: where a query chooses, and for the loss
        select = m + t > topk
        if select or self.learner:
            with jax.named_scope("dsa_indexer"):
                scores = sparse.indexer_scores(
                    rope(q_idx, jnp.arange(t), c.rope_theta).transpose(
                        0, 2, 1, 3).astype(dt),
                    w_idx.transpose(0, 2, 1),
                    rope(both[..., 2 * groups * e:], key_pos,
                         c.rope_theta).astype(dt),
                    pad + m, bq, bk)
        with jax.named_scope("dsa_select"):
            seen = jnp.sum(visible, axis=-1, dtype=jnp.int32)    # (B, T)
            if select:
                shown = jnp.where(visible, jax.lax.stop_gradient(scores),
                                  -jnp.inf)
                value, cut = sparse.kth_largest(
                    shown, jnp.minimum(seen, topk), topk)
                value = checkpoint_name(value, THRESHOLD)
                cut = checkpoint_name(cut, CUT)
                above = shown - value[..., None]
                # ties at the threshold: the earlier keys, up to k
                above = jnp.where((above == 0) & (jnp.arange(s)[None, None]
                                                  > cut[..., None]),
                                  -jnp.inf, above)
            else:
                above = jnp.where(visible, 0.0, -jnp.inf)
            chosen = above >= 0
        # what each query chose, for a caller that asks (``intermediates``
        # mutable): the comparison with the reference's choice
        self.sow("intermediates", "chosen", chosen[:, :, pad:])

        with jax.named_scope("dsa_attn"):
            out, lse = sparse.sparse_attention(q_rot, keys, values, above,
                                               e ** -0.5, bq, bk)
        out = out.reshape(b, groups, n, t, e).transpose(0, 3, 1, 2, 4)
        with jax.named_scope("gqa_attn"):
            out = matmul("btgne,gned->btd", out, w_o, dt, _F32)
        if not self.learner:
            return out, rows, None

        with jax.named_scope("dsa_kl"):
            probs = sparse.chosen_probs(q_rot, keys, lse, above, e ** -0.5,
                                        bq, bk)
            logits = jnp.where(chosen, scores, -jnp.inf)
            log_q = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
            kl = jnp.sum(jnp.where(
                chosen, jax.scipy.special.xlogy(probs, probs)
                - probs * jnp.where(chosen, log_q, 0.0), 0.0), axis=-1)
            stats = _counters(chosen, visible, seen > topk, key_pos, t, topk,
                              c.q_chunk_size, c.kv_chunk_size)
        return out, rows, {**stats, "loss": kl}


class MoE(experts.RoutedMoE):
    """This source's expert layer: ``experts.RoutedMoE`` with no shared
    expert, no epsilon in the weights' normalisation, and overflow chunks of
    ``CHUNK_ROWS``."""
    topk_eps: float = 0.0
    shared: bool = False
    out_init: Any = _INIT_OUT

    @property
    def chunk_rows(self) -> int:
        return CHUNK_ROWS


class Layer(nn.Module):
    """One pre-norm layer: ``x += Attn(norm(x)); x += FFN(norm(x))``;
    ``stored`` is its part of the state row. Returns the stream, the part
    as the window leaves it, the router's counters (None for a dense
    layer) and the attention's (None but in the learner's call)."""
    core: CoreConfig
    dtype: Any
    dense: bool
    window_stats: bool

    @nn.compact
    def __call__(self, x, stored):
        c, dt = self.core, self.dtype
        d = x.shape[-1]
        w_attn = self.param("input_layernorm", nn.initializers.ones, (d,))
        w_ffn = self.param("post_attention_layernorm", nn.initializers.ones,
                           (d,))
        out, rows, dsa = SparseAttention(c, dt, self.window_stats,
                                         name="self_attn")(
            rms_norm(x, w_attn, c.rms_norm_eps), stored)
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
        return x + out, new, stats, dsa


class SparseAttnMoeStack(nn.Module):
    """Input projection, the layers (each under ``jax.checkpoint``, which
    keeps the layer's inputs and the arrays ``KEPT`` names: the attention's
    output (B, H, T, e) and log-sum-exp (B, H, T), each query's threshold
    and cut (B, T); the backward recomputes the rest of the layer, the
    indexer's scores and the heads' probabilities among it, whose (B, T, S)
    float32 arrays would not fit kept beside the window), the final norm;
    the state row in and out, a layer's ``memory_len`` x ``row_width``
    floats after another's. The expert layers' routing counters are sown
    into the ``moe`` collection, the attention's counters and the indexer's
    loss into ``core`` (``NetworkApply.apply_learner`` reads both)."""
    core: CoreConfig
    dtype: Any
    window_stats: bool

    @nn.compact
    def __call__(self, x_seq, state):
        c = self.core
        b = x_seq.shape[0]
        part = c.memory_len * row_width(c)
        flat = state.astype(_F32).reshape(b, -1)
        w_in = self.param("input_proj", nn.initializers.lecun_normal(),
                          (x_seq.shape[-1], c.hidden_size))
        x = Norm(c.rms_norm_eps, name="input_norm")(
            matmul("btd,de->bte", x_seq, w_in, self.dtype, _F32))
        new_parts, stats, dsa = [], [], []
        for i in range(c.num_hidden_layers):
            stored = flat[:, i * part:(i + 1) * part].reshape(
                b, c.memory_len, row_width(c))
            x, new, s, a = nn.remat(Layer, policy=_KEEP)(
                c, self.dtype, dense=i < c.first_k_dense_replace,
                window_stats=self.window_stats, name=f"layers_{i}")(x, stored)
            new_parts.append(new.reshape(b, -1))
            if s is not None:
                stats.append(s)
            if a is not None:
                dsa.append(a)
        stack = lambda found: jax.tree_util.tree_map(  # noqa: E731
            lambda *xs: jnp.stack(xs), *found)
        if stats:
            self.sow("moe", "counters", stack(stats))
        if dsa:
            self.sow("core", "counters", stack(dsa))
        y = Norm(c.rms_norm_eps, name="norm")(x).astype(self.dtype)
        return y, jnp.concatenate(new_parts, axis=1).reshape(b, 2, -1)


@dataclasses.dataclass(frozen=True)
class SparseAttnMoeCore:
    core: CoreConfig
    dtype: Any
    scope = SCOPE

    @property
    def state_half(self) -> int:
        # head_dim and index_head_dim are even
        return self.core.num_hidden_layers * self.core.memory_len \
            * row_width(self.core) // 2

    @property
    def out_dim(self) -> int:
        return self.core.hidden_size

    @property
    def routes_experts(self) -> bool:
        return self.core.num_hidden_layers > self.core.first_k_dense_replace

    def state_parts(self) -> List[Tuple[str, int, int]]:
        c = self.core
        layers, kv = c.num_hidden_layers, 2 * c.num_key_value_heads * c.head_dim
        return [("key_value_window", layers, layers * c.memory_len * kv),
                ("indexer_keys", layers,
                 layers * c.memory_len * c.index_head_dim)]

    def init_state(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch, 2, self.state_half), _F32)

    def unroll(self, x_seq: jnp.ndarray, state: jnp.ndarray,
               window_stats: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return SparseAttnMoeStack(self.core, self.dtype, window_stats,
                                  name=self.scope)(x_seq, state)

    def record_block(self, batch: int, window: int) -> Dict[str, Any]:
        """The sparse attention's sizes, and the bytes of each array
        ``KEPT`` names that the layers' remat keeps through a train step of
        ``batch`` windows of ``window`` positions, all layers (the
        threshold and cut only where a query chooses), for the record's
        one-shot ``core`` block."""
        c = self.core
        rows = c.num_hidden_layers * batch * window
        heads = rows * c.num_attention_heads
        kept = {sparse.OUT: heads * c.head_dim * np.dtype(self.dtype).itemsize,
                sparse.LSE: heads * 4}
        if c.memory_len + window > c.index_topk:
            kept.update({THRESHOLD: rows * 4, CUT: rows * 4})
        return {"dsa": {"index_topk": c.index_topk,
                        "index_n_heads": c.index_n_heads,
                        "index_head_dim": c.index_head_dim,
                        "q_chunk_size": c.q_chunk_size,
                        "kv_chunk_size": c.kv_chunk_size},
                "remat": {"kept": kept, "bytes": sum(kept.values())}}

    def summarise(self, sums: Dict[str, np.ndarray], steps: int
                  ) -> Dict[str, Any]:
        """The record's ``dsa`` block from the sums, over ``steps`` train
        steps, of the per-layer counters ``SparseAttention`` sows, per
        layer: the share of queries that chose (saw more than
        ``index_topk`` keys), the mean keys a query attended, the share of
        chosen keys more than ``index_topk`` positions back (how far the
        learned choice is from a sliding window), the key blocks the kernel
        skipped beyond the causal ones, and the indexer's loss (mean over
        steps); beside them ``steps`` and ``pairs``, the chosen (query,
        key) pairs of all layers and steps, which ``k_sparse_attn_roofline``
        counts the attention's work by."""
        layers = [{
            "selecting_share": float(sums["selecting"][i]
                                     / sums["queries"][i]),
            "keys_per_query": float(sums["pairs"][i] / sums["queries"][i]),
            "far_share": float(sums["far"][i] / max(sums["pairs"][i], 1.0)),
            "blocks_skipped": int(sums["blocks_skipped"][i]),
            "indexer_loss": float(sums["loss"][i] / steps),
        } for i in range(len(sums["pairs"]))]
        return {"dsa": {"steps": steps, "pairs": int(sums["pairs"].sum()),
                        "layers": layers}}
