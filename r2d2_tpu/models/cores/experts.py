"""The held experts, and the plain parts every layer stack is built of, for the
cores that route to experts (``mla_moe.py``, ``conv_attn_moe.py``).

An expert layer here is: a router over all ``n_routed_experts`` that scores
by sigmoid, chooses ``num_experts_per_tok`` of them by score plus a bias that
only the choice reads, and weights the chosen by their own scores normalised
over the chosen and scaled (``route``); the experts this chip holds
(``expert_offset .. expert_offset + experts_held - 1``) computed for the
(position, expert) pairs that fall on them, what the others would add left
out (``HeldExperts``); and, where the caller's source has one, a shared
expert every position goes through (``RoutedMoE``). What differs between the
sources is an argument of the caller and no option of the program: the
epsilon of the weights' normalisation, whether there is a shared expert, the
initialiser of the projections into the residual stream (it follows the
published depth), and the rows an overflow chunk of the walk takes.

Held experts: no pair is dropped and there is no capacity factor: all pairs
are sorted by expert (pairs on absent experts last), and the held ones go
through grouped matrix products (``held_experts_ffn``): first one chunk of
sorted rows that holds the pairs a layer expects here, which the shapes give
(``first_chunk_rows``: positions x top-k x held / routed, and a margin), then
overflow chunks of the core's ``CHUNK_ROWS`` rows, as many as the pairs past
the first chunk fill. What a chunk costs beyond its rows (the weights read,
the positions' sums streamed, the weights' gradient written) is paid once a
layer-pass at the expected load, and a router that runs over it pays in
small steps. The way back follows the same pairs: each chunk adds its rows
to their positions' float32 sums as it goes (``add_rows``), forward and
backward, so no array has a row for every pair and a chip that holds an
eighth of the experts moves an eighth of the rows.

The router reads what varies between positions. An agent's stream is not a
language model's: the torso's latents are rectified, so every position
shares a large common part (nine tenths of the router's input on frames of
noise), every expert's score then moves with it, all positions choose the
same experts, and the router's gradient, which is that common part times a
sum over positions, drives the collapse on with every optimizer step
(PERF.md, Findings, PR 27). So the router's input is h less its mean m over
positions. With ``window_stats`` (the learner) m is the mean over the call's
own positions, and comes back among the counters; the train step stores it
in ``router_input_mean`` (``store_router_means``), which is what a call
without ``window_stats`` (acting) subtracts. No gradient flows into m. No
source has such a term.

Precision: matrix products take ``dtype`` operands (bf16 on a TPU) and
accumulate in float32; the norms, the router's scores and the experts'
combine are float32.

Scopes (the device trace's rows): ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_shared``, ``moe_combine``. The counters of a stack's
expert layers are sown into the ``moe`` collection (``moe_counters`` reads
them). Parameter names are the ``mla_moe`` core's (``gate``,
``e_score_correction_bias``, ``router_input_mean``, ``experts/gate_up_proj``,
``experts/down_proj``, ``shared_experts/...``), so its checkpoints hold.
"""

import functools
import math
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.config import CoreConfig
from r2d2_tpu.ops import pallas_kernels
from r2d2_tpu.ops.pallas_kernels import add_rows, sum_rows

# every core builds its modules under this scope (the module's name, the
# device trace's scope and the parameter group's name)
SCOPE = "mem_core"
# the source families' ``initializer_range``
INIT = nn.initializers.normal(0.02)
_F32 = jnp.float32


def residual_init(published_depth: int):
    """The initialiser of the projections that write into the residual
    stream (an attention's output, every down projection): narrower by
    sqrt(2 x the published depth), the residual scaling of GPT-2 and
    Megatron, so that the stream keeps its size through the layers."""
    return nn.initializers.normal(0.02 / (2 * published_depth) ** 0.5)


def matmul(spec: str, x, w, dtype, out_dtype=None):
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



class Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, w, self.eps)


class SwiGLU(nn.Module):
    """``h`` (N, d), or the residual stream with the norm's weight to put
    in front (``eps`` its epsilon). ``out_init`` draws the down projection
    (``residual_init`` of the caller's published depth)."""
    width: int
    dtype: Any
    out_init: Any
    eps: float = 0.0

    @nn.compact
    def __call__(self, h, norm_weight=None):
        if norm_weight is not None:
            h = rms_norm(h, norm_weight, self.eps)
        d = h.shape[-1]
        gate = self.param("gate_proj", INIT, (d, self.width))
        up = self.param("up_proj", INIT, (d, self.width))
        down = self.param("down_proj", self.out_init, (self.width, d))
        a = (jax.nn.silu(matmul("nd,df->nf", h, gate, self.dtype
                                ).astype(_F32))
             * matmul("nd,df->nf", h, up, self.dtype).astype(_F32))
        return matmul("nf,fd->nd", a, down, self.dtype, _F32)


def route(scores, bias, top_k: int, scale: float, eps: float):
    """(chosen (N, k) int32, weights (N, k) float32) from the router's
    sigmoid scores (N, routed): the top-k of scores + bias, weighted by
    their own scores normalised over the chosen (``eps`` in the
    denominator, the source's) and scaled."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + eps) * scale
    return chosen, weights


def grouped_matmul(rows, weights, group_sizes, dtype, out_dtype=None,
                   transposed=False):
    """Rows sorted by group times their group's matrix: rows (M, k),
    weights (G, k, n), or (G, n, k) ``transposed`` (contracted over their
    last axis, so the weights as they are kept serve the backward too),
    ``group_sizes`` (G,) -> (M, n): ``dtype`` operands accumulated in
    float32, the result in ``out_dtype`` (``dtype`` where none is given);
    rows past the groups' total are undefined. On a TPU the grouped product
    of ``ops/pallas_kernels.py``, whose row tile fits groups of some hundred
    rows; elsewhere, for shapes that kernel does not tile and for calls of
    few rows (the overflow chunks, acting) ``jax.lax.ragged_dot`` (PERF.md,
    Findings, PR 37)."""
    return pallas_kernels.grouped_matmul(
        rows.astype(dtype), weights.astype(dtype), group_sizes, transposed,
        out_dtype or dtype)


def grouped_outer(rows, cots, group_sizes, dtype):
    """The gradient of ``grouped_matmul``'s weights: each group's rows (M, k)
    transposed times its rows of ``cots`` (M, n) -> (G, k, n), ``dtype``
    operands, float32 accumulated and float32 out: a sum over pairs is kept
    in float32, never rounded on its way to the optimizer."""
    return pallas_kernels.grouped_outer(rows.astype(dtype),
                                        cots.astype(dtype), group_sizes)


def _activation(gu):
    """SwiGLU's elementwise middle on the first product's [gate | up]."""
    width = gu.shape[1] // 2
    return (jax.nn.silu(gu[:, :width].astype(_F32))
            * gu[:, width:].astype(_F32)).astype(gu.dtype)


def _chunk_ffn(x, weight, gate_up, down, sizes, live):
    """The experts' SwiGLU on one chunk of sorted rows, two grouped
    products, each row times its pair's routing weight. ``live`` (rows, 1)
    marks the rows that stand for a pair on a held expert; a grouped
    product leaves the rows past its groups undefined, so those read zero
    and give no gradient."""
    x = jnp.where(live, x, 0)
    with jax.named_scope("moe_experts"):
        act = _activation(grouped_matmul(x, gate_up, sizes, x.dtype))
        out = grouped_matmul(act, down, sizes, x.dtype, _F32)
    return jnp.where(live, out * weight[:, None], 0).astype(x.dtype)


def _chunk_ffn_back(x, weight, gate_up, down, sizes, live, g):
    """``_chunk_ffn``'s backward for the rows' gradient ``g``: (the rows'
    gradient in ``x``'s dtype, the routing weights' (rows,) float32, and
    the two weights' gradients in float32, as the grouped products
    accumulate them). The two products against the weights transposed
    contract the weights as they are kept over their last axis: no
    transposed copy is made. It recomputes the activation, not the second
    product: with t = g times ``down`` transposed, a row's routing weight
    has the gradient t . act and its activation weight x t."""
    dt = x.dtype
    x, g = jnp.where(live, x, 0), jnp.where(live, g, 0)
    with jax.named_scope("moe_experts"):
        gu = grouped_matmul(x, gate_up, sizes, dt)
        act, middle_back = jax.vjp(_activation, gu)
        t = jnp.where(live, grouped_matmul(g, down, sizes, dt, _F32, True),
                      0)
        d_weight = jnp.sum(t * act.astype(_F32), axis=-1)
        d_down = grouped_outer(act, g.astype(_F32) * weight[:, None], sizes,
                               dt)
        (d_gu,) = middle_back((t * weight[:, None]).astype(dt))
        d_x = jnp.where(live, grouped_matmul(d_gu, gate_up, sizes, dt,
                                             transposed=True), 0)
        d_gate_up = grouped_outer(x, d_gu, sizes, dt)
    return d_x, d_weight, d_gate_up, d_down


def _chunk_of(lo, rows: int, h, order, pair_weight, group_sizes):
    """The ``rows`` sorted pairs from ``lo`` on: (its pairs, the position
    each row's sum goes to, ``len(h)`` where the row stands for no pair on a
    held expert; its positions' rows of ``h``, its pairs' weights, the
    groups' sizes inside it, its live rows)."""
    pairs = jax.lax.dynamic_slice_in_dim(order, lo, rows)
    ends = jnp.cumsum(group_sizes)
    sizes = (jnp.clip(ends, lo, lo + rows)
             - jnp.clip(ends - group_sizes, lo, lo + rows))
    live = lo + jnp.arange(rows) < ends[-1]
    n = h.shape[0]
    at = pairs % n
    with jax.named_scope("moe_dispatch"):
        x = h[at]
        w = pair_weight[jnp.minimum(pairs, pair_weight.shape[0] - 1)]
    return pairs, jnp.where(live, at, n), x, w, sizes, live[:, None]


# The first chunk's room over the pairs a layer expects, and the tile its
# rows are rounded up to. The tile is the grouped product's row tile
# (``ops/pallas_kernels.py``: 256 rows, which the cells' groups of 750 | 1,000
# rows fill about 0.76 | 0.80 full where tiles of 512 would be 0.60 | 0.66
# full; PERF.md, Findings, PR 37), so every chunk is whole tiles. The margin: a
# centred router's layers hold up to 6% more than the expectation as their
# mean over a run (it moves with the seed) and 1% more or less from step to
# step; the first overflow chunk costs a layer 4 to 5 ms forward and backward
# (its program's own accumulators and their add), a tile more of first chunk
# a tenth of that, the products passing over the tiles that no pair reached;
# so the margin is worth its rows as soon as one layer-pass in some twenty
# would run over. With tiles of 256, 8% makes 6,656 rows of 6,000 expected and
# 8,704 of 8,000, the rows PR 34 fitted at tiles of 512 and 5%, which no
# layer of 160 steps of either cell ran over (the largest step held 6,483 |
# 8,349 pairs: a tile less, 6,400 | 8,448, would have run over in the one
# cell and come within 100 rows of it in the other).
FIRST_CHUNK_MARGIN = 0.08
ROW_TILE = pallas_kernels.GROUPED_ROW_TILE


def first_chunk_rows(positions: int, core: CoreConfig) -> int:
    """Sorted rows the walk's first chunk takes, from the shapes alone: the
    pairs a layer expects on the experts held here (every expert as likely
    as another), ``FIRST_CHUNK_MARGIN`` more, in whole ``ROW_TILE``s, and
    never more than all pairs."""
    pairs = positions * core.num_experts_per_tok
    expected = pairs * core.experts_held / core.n_routed_experts
    tiles = math.ceil(expected * (1 + FIRST_CHUNK_MARGIN) / ROW_TILE)
    return min(tiles * ROW_TILE, pairs)


def _overflow_chunks(group_sizes, first: int, chunk: int):
    """Chunks of ``chunk`` rows that the pairs past the first chunk fill
    (``chunk`` 0: the first chunk holds every pair there can be)."""
    if not chunk:
        return jnp.zeros((), jnp.int32)
    return (jnp.maximum(jnp.sum(group_sizes) - first + chunk - 1, 0)
            // chunk).astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def held_experts_ffn(h, order, pair_weight, gate_up, down, group_sizes,
                     first: int, chunk: int):
    """The held experts' weighted SwiGLU, summed to the positions.

    ``h`` (N, d) the positions; ``order`` (M,) the pairs sorted by expert,
    the pairs on absent experts last, padded to the walk's whole chunks
    with numbers past the pairs' (pair = choice * N + position);
    ``pair_weight`` (N * top_k,) the pairs' routing weights in pair order;
    gate_up (G, d, 2f), down (G, f, d); ``group_sizes`` (G,) the pairs on
    each held expert. Returns each position's sum over its pairs on held
    experts (N, d) float32, and the number of rows the chunks it walked
    took in as pairs.

    The walk: one chunk of the first ``first`` sorted rows, which holds the
    pairs a layer expects (``first_chunk_rows``) and runs outside any loop,
    then as many chunks of ``chunk`` rows as the pairs past it fill (a loop
    with a dynamic trip count; ``chunk`` 0: the first chunk holds every
    pair). A chunk gathers its positions' rows and its pairs' weights, runs
    the two grouped products and adds its rows to their positions' sums
    (``add_rows``; the first chunk's start at zero: ``sum_rows``). What a
    chunk costs beyond its rows (the weights read, the sums streamed through
    ``add_rows``, in the backward the weights' gradient written) a layer at
    its expected load pays once, and a load above it pays in small steps.
    So the work follows the pairs that are
    here, there and back, whatever the router's skew, with static shapes and
    no pair dropped: no array has a row for every pair. The backward is its
    own: it walks the same chunks, gathers a chunk's positions' rows of the
    sum's gradient, recomputes the chunk's activation and adds the rows'
    gradients to their positions as the forward does. The first chunk's
    gradient of the weights, float32 as the grouped product accumulates it,
    is the gradient where nothing runs over; where something does, another
    program (a ``lax.cond`` on the overflow chunks' number) sums the
    overflow chunks' into float32 arrays of its own and adds them to it."""
    # the weights come as the parameters are kept (float32) and are cast
    # here, once a call, so that their gradient goes back uncast
    gate_up, down = gate_up.astype(h.dtype), down.astype(h.dtype)

    def one(lo, rows, total, covered):
        _, pos, x, w, sizes, live = _chunk_of(lo, rows, h, order,
                                              pair_weight, group_sizes)
        out = _chunk_ffn(x, w, gate_up, down, sizes, live)
        with jax.named_scope("moe_combine"):
            # the first chunk's sums start at zero: none are read
            total = (sum_rows(out, pos, h.shape[0]) if total is None
                     else add_rows(total, out, pos))
        return total, covered + jnp.sum(live, dtype=jnp.int32)

    walked = one(0, first, None, jnp.zeros((), jnp.int32))
    if not chunk:
        return walked
    return jax.lax.fori_loop(
        0, _overflow_chunks(group_sizes, first, chunk),
        lambda i, carry: one(first + i * chunk, chunk, *carry), walked)


def _held_experts_fwd(h, order, pair_weight, gate_up, down, group_sizes,
                      first, chunk):
    return (held_experts_ffn(h, order, pair_weight, gate_up, down,
                             group_sizes, first, chunk),
            (h, order, pair_weight, gate_up, down, group_sizes))


def _held_experts_bwd(first, chunk, res, g):
    g, _ = g                                # the count carries no gradient
    h, order, pair_weight, gate_up, down, group_sizes = res
    kept = gate_up.dtype, down.dtype
    gate_up, down = gate_up.astype(h.dtype), down.astype(h.dtype)

    def one(lo, rows, dh, dw):
        pairs, pos, x, w, sizes, live = _chunk_of(lo, rows, h, order,
                                                  pair_weight, group_sizes)
        with jax.named_scope("moe_combine"):
            # a row's gradient is its position's
            gi = g[pairs % g.shape[0]].astype(h.dtype)
        dxi, dwi, dw1, dw2 = _chunk_ffn_back(x, w, gate_up, down, sizes, live,
                                             gi)
        with jax.named_scope("moe_dispatch"):
            dh = (sum_rows(dxi, pos, h.shape[0]) if dh is None
                  else add_rows(dh, dxi, pos))
            # a permutation's numbers and, past them, the padding's
            dw = dw.at[pairs].set(dwi, mode="drop", unique_indices=True)
        return dh, dw, dw1, dw2

    overflow_chunks = _overflow_chunks(group_sizes, first, chunk)

    def walk(run_over: bool):
        dh, dw, dw1, dw2 = one(0, first, None, jnp.zeros_like(pair_weight))
        if not run_over:
            return dh, dw, dw1, dw2

        def overflow(i, carry):
            dh, dw, over1, over2 = carry
            dh, dw, dw1i, dw2i = one(first + i * chunk, chunk, dh, dw)
            return dh, dw, over1 + dw1i, over2 + dw2i

        # the overflow chunks' sum starts from zeros of its own and is added
        # to the first chunk's at the end: a loop that carries the first
        # chunk's product and adds to it makes XLA lay the parameters out
        # transposed, copy every float32 array the optimizer touches and
        # recompute forward fusions for want of memory (CPU, count, PR 34)
        dh, dw, over1, over2 = jax.lax.fori_loop(
            0, overflow_chunks, overflow,
            (dh, dw, jnp.zeros_like(dw1), jnp.zeros_like(dw2)))
        return dh, dw, dw1 + over1, dw2 + over2

    # two programs, so that a walk that stays inside its first chunk pays
    # nothing for the other's accumulators
    dh, dw, dw1, dw2 = (jax.lax.cond(overflow_chunks > 0, lambda: walk(True),
                                     lambda: walk(False))
                        if chunk else walk(False))
    return (dh.astype(h.dtype), None, dw, dw1.astype(kept[0]),
            dw2.astype(kept[1]), None)


held_experts_ffn.defvjp(_held_experts_fwd, _held_experts_bwd)


class HeldExperts(nn.Module):
    """The routed experts this chip holds, for the pairs that fall on them:
    all N*k (position, expert) pairs are sorted by expert, the pairs on
    absent experts last; the held ones go through ``held_experts_ffn``, the
    first ``first_chunk_rows`` sorted rows at once and what runs over
    ``chunk_rows`` at a time, which gives each position's sum. Beside it the
    layer's counters: ``dropped``, the pairs the router put on held experts
    less the rows the chunks took in (none: there is no capacity to run out
    of), ``rows_walked``, the sorted rows of the chunks that were walked
    (the first and the overflow chunks), and ``overflow_chunks``, how many
    of the latter."""
    core: CoreConfig
    dtype: Any
    chunk_rows: int
    out_init: Any

    @nn.compact
    def __call__(self, h, chosen, weights):
        c, dt = self.core, self.dtype
        n, d = h.shape
        k, held, width = (c.num_experts_per_tok, c.experts_held,
                          c.moe_intermediate_size)
        gate_up = self.param("gate_up_proj", INIT, (held, d, 2 * width))
        down = self.param("down_proj", self.out_init, (held, width, d))
        first = first_chunk_rows(n, c)
        # 0: the first chunk holds every pair there can be
        chunk = min(self.chunk_rows, n * k - first)
        padded = first + (chunk and -(-(n * k - first) // chunk) * chunk)

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
        routed, covered = held_experts_ffn(
            h.astype(dt), order, pair_weight, gate_up, down, group_sizes,
            first, chunk)
        overflow = _overflow_chunks(group_sizes, first, chunk)
        return routed, {
            "dropped": jnp.sum(on_held, dtype=jnp.int32) - covered,
            "rows_walked": first + overflow * chunk,
            "overflow_chunks": overflow}


class RoutedMoE(nn.Module):
    """An expert layer's feed-forward half on the residual stream ``x``
    (B, T, d) with its norm's weight: router, held experts, the shared
    expert where there is one, and the layer's counters (the router's, the
    walk's, and ``tile_rows``, the rows of the row tiles a grouped product
    visits for the held experts' groups, ``ROW_TILE`` x the visits, a tile
    that two experts' pairs share counted for each: the pairs here over it
    is how full the MXU's row tiles are). A core's file
    fixes what its source fixes in a subclass: ``topk_eps``, ``shared``
    (the shared expert is ``n_shared_experts`` experts wide), ``out_init``
    and ``chunk_rows``."""
    core: CoreConfig
    dtype: Any
    window_stats: bool
    topk_eps: float
    shared: bool
    out_init: Any

    @property
    def chunk_rows(self) -> int:
        """Rows of an overflow chunk: what the walk takes at a time of the
        sorted pairs past its first chunk."""
        raise NotImplementedError

    @nn.compact
    def __call__(self, x, norm_weight):
        c = self.core
        b, t, d = x.shape
        flat = rms_norm(x, norm_weight, c.rms_norm_eps).reshape(b * t, d)
        with jax.named_scope("moe_router"):
            w_r = self.param("gate", INIT, (d, c.n_routed_experts))
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
            chosen, weights = route(scores, bias, c.num_experts_per_tok,
                                    c.routed_scaling_factor, self.topk_eps)
        routed, walk = HeldExperts(c, self.dtype, self.chunk_rows,
                                   self.out_init, name="experts")(
            flat, chosen, weights)
        if self.shared:
            with jax.named_scope("moe_shared"):
                shared = SwiGLU(c.moe_intermediate_size * c.n_shared_experts,
                                self.dtype, self.out_init,
                                name="shared_experts")(flat)
            with jax.named_scope("moe_combine"):
                routed = routed + shared
        out = routed.reshape(b, t, d)
        with jax.named_scope("moe_router"):
            share = scores / scores.sum(-1, keepdims=True)
            histogram = jnp.sum(jax.nn.one_hot(
                chosen, c.n_routed_experts, dtype=jnp.int32), axis=(0, 1))
            stats = {
                "chosen": histogram,
                # the rows of the row tiles a grouped product visits for
                # the held experts' groups: from their sizes and the tile
                "tile_rows": pallas_kernels.tile_rows_visited(
                    histogram[c.expert_offset:
                              c.expert_offset + c.experts_held], ROW_TILE),
                "entropy": -jnp.mean(jnp.sum(share * jnp.log(share + 1e-30),
                                             axis=-1)),
                "input_mean": mean,
                **walk,
            }
        return out, stats


def moe_counters(mutated: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """The stack's sown counters out of ``apply(..., mutable=['moe'])``'s
    second result: {chosen (L_moe, routed), entropy (L_moe,), dropped
    (L_moe,), rows_walked (L_moe,), overflow_chunks (L_moe,), tile_rows
    (L_moe,), input_mean (L_moe, hidden)}, or {} for a stack without expert
    layers."""
    found = jax.tree_util.tree_leaves(
        mutated.get("moe", {}), is_leaf=lambda x: isinstance(x, tuple))
    return found[0][0] if found else {}


def store_router_means(params, means):
    """``params`` with each expert layer's ``router_input_mean`` set to its
    row of ``means`` (L_moe, hidden), the ``input_mean`` counter of a
    forward pass with ``window_stats``: what acting centres the router's
    input on from then on. The expert layers are the core's layers that
    hold a router, whichever core it is."""
    core = dict(params["params"][SCOPE])
    layers = sorted((name for name in core if name.startswith("layers_")
                     and "router_input_mean" in core[name]["mlp"]),
                    key=lambda name: int(name.rsplit("_", 1)[1]))
    for name, mean in zip(layers, means):
        core[name] = {**core[name], "mlp": {**core[name]["mlp"],
                                            "router_input_mean": mean}}
    return {**params, "params": {**params["params"], SCOPE: core}}
