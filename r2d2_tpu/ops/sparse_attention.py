"""Learned sparse attention in blocks (DeepSeek-V3.2's sparse attention, as
Keye-VL-2.0's ``sa_config`` sizes it): the lightning indexer's scores, the
exact top-k threshold of each query's row, attention over the chosen keys,
and the heads' attention summed per (query, key) for the indexer's loss.

Shapes: queries ``q`` (B, H, T, e) in H = G x n heads, query head h reading
key/value head h // n; keys and values (B, G, S, e); the indexer's queries
(B, J, T, c), its weights (B, J, T), its keys (B, S, c). ``d`` (B, T, S)
float32 says which keys a query attends: the chosen are those with
``d >= 0`` (the core hands the indexer's score less the query's threshold,
and -inf where a key is not visible). Nothing here knows positions: the
core's masks are in ``d``, and the indexer's kernels skip the key blocks
past a query block's last visible key from ``offset``, the index of the key
at the window's first position.

On a TPU each function is a Pallas kernel (``jax.lax.platform_dependent``)
where the shapes tile (queries in blocks of ``bq`` rows, keys in blocks of
``bk``, heads 128 lanes wide; the indexer's heads are padded with zeros to
128), and plain ``jax.numpy`` over whole arrays elsewhere (the CPU tests, the
rehearsal, the actor's one-step window): the same mathematics, which the
tests hold the kernels to in interpret mode. No kernel writes an array of
queries x keys x heads: a block's scores live in VMEM, per head.

  * ``indexer_scores``: I[b,t,s] = sum_j w[b,j,t] relu(qI[b,j,t] . kI[b,s])
    (the caller folds the scales into w), float32; its own backward.
  * ``kth_largest``: each row's k-th largest value, k a row's own, and the
    last key kept among those equal to it, so that a row keeps exactly k
    (``jax.lax.top_k`` off the TPU; on it a bisection over the values' bits,
    32 counts of a row held in VMEM, and where a row holds ties at its
    value, 15 more over the keys' indices).
  * ``sparse_attention``: softmax over the chosen keys, returning the output
    and each (head, query)'s log-sum-exp; its backward is two kernels, the
    queries' gradient and the keys' and values' (flash attention's). A
    block of ``bq`` x ``bk`` (query, key) pairs that holds no chosen pair is
    skipped (``blocks_live``), forward and backward. Under a gradient the
    output and the log-sum-exp carry the names ``OUT`` and ``LSE``, which a
    remat policy may keep (the sparse core's does).
  * ``chosen_probs``: the mean over the H heads of each head's attention
    probability of (query, key), zero off the chosen: the distribution the
    indexer's loss holds its scores to. No gradient.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_F32 = jnp.float32
LANES = 128
# a score no chosen key can have: finite, so that a row that has seen only
# such keys so far makes no NaN (flash attention's value)
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_VMEM_BYTES = 64 * 2**20
_TRANS_B = (((1,), (1,)), ((), ()))
# rows of a query block the threshold's bisection holds at once
SELECT_ROWS = 64
# the names of ``sparse_attention``'s output and log-sum-exp under a gradient
OUT, LSE = "sparse_attn_out", "sparse_attn_lse"


def _lanes(x):
    """(..., T) -> (..., T, 128): a per-row value as a kernel reads it."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def _cols(x, width: int):
    """A (rows, 128) per-row value widened (or cut) to ``width`` columns."""
    if width >= LANES:
        return jnp.tile(x, (1, width // LANES))
    return x[:, :width]


def tiles(t: int, s: int, bq: int, bk: int, width: int) -> bool:
    """Whether the kernels tile these shapes: whole blocks of queries and
    keys, blocks the TPU's (16, 128) tiles fill, heads of whole lanes."""
    return (t % bq == 0 and s % bk == 0 and bq % 16 == 0 and bk % LANES == 0
            and width % LANES == 0)


def _pad_lanes(x):
    extra = -x.shape[-1] % LANES
    if not extra:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


# ---------------------------------------------------------------------------
# the indexer's scores


def indexer_reference(q, w, k):
    """I (B, T, S) float32 over whole arrays."""
    s = jnp.einsum("bjtc,bsc->bjts", q, k, preferred_element_type=_F32)
    return jnp.einsum("bjt,bjts->bts", w.astype(_F32), jax.nn.relu(s))


def _causal(qi, kb, bq: int, bk: int, offset: int):
    """Whether key block ``kb`` holds a key at or before query block
    ``qi``'s last position."""
    return kb * bk <= offset + (qi + 1) * bq - 1


def _indexer_kernel(q_ref, w_ref, k_ref, o_ref, *, bq, bk, offset):
    from jax.experimental import pallas as pl
    qi, kb = pl.program_id(1), pl.program_id(2)
    live = _causal(qi, kb, bq, bk, offset)

    @pl.when(live)
    def _():
        k = k_ref[...]
        acc = jnp.zeros((bq, bk), _F32)
        for j in range(q_ref.shape[0]):
            s = jax.lax.dot_general(q_ref[j], k, _TRANS_B,
                                    preferred_element_type=_F32)
            acc = acc + _cols(w_ref[j], bk) * jnp.maximum(s, 0.0)
        o_ref[...] = acc

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _indexer_back_kernel(q_ref, w_ref, k_ref, g_ref, dq_ref, dw_ref, dk_ref,
                         *, bq, bk, offset):
    from jax.experimental import pallas as pl
    qi, kb = pl.program_id(1), pl.program_id(2)
    live = _causal(qi, kb, bq, bk, offset)

    @pl.when(kb == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live)
    def _():
        k, g = k_ref[...], g_ref[...]
        dk = jnp.zeros(dk_ref.shape, _F32)
        for j in range(q_ref.shape[0]):
            q = q_ref[j]
            s = jax.lax.dot_general(q, k, _TRANS_B,
                                    preferred_element_type=_F32)
            dw_ref[j] += jnp.sum(g * jnp.maximum(s, 0.0), axis=1)[:, None]
            gj = jnp.where(s > 0, g * _cols(w_ref[j], bk), 0.0)
            dq_ref[j] += jax.lax.dot(gj.astype(k.dtype), k,
                                     preferred_element_type=_F32)
            dk = dk + jax.lax.dot(gj.T.astype(q.dtype), q,
                                  preferred_element_type=_F32)
        dk_ref[...] = dk

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)


def _indexer_specs(j, c, bq, bk):
    from jax.experimental import pallas as pl
    return [pl.BlockSpec((None, j, bq, c), lambda b, i, kb: (b, 0, i, 0)),
            pl.BlockSpec((None, j, bq, LANES), lambda b, i, kb: (b, 0, i, 0)),
            pl.BlockSpec((None, bk, c), lambda b, i, kb: (b, kb, 0))]


def indexer_pallas(q, w, k, offset: int, bq: int, bk: int,
                   interpret: bool = False):
    """The kernel of ``indexer_scores``'s forward; key blocks past a query
    block's last visible key are zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    q, k = _pad_lanes(q), _pad_lanes(k)
    (b, j, t, c), s = q.shape, k.shape[1]
    return pl.pallas_call(
        functools.partial(_indexer_kernel, bq=bq, bk=bk, offset=offset),
        grid=(b, t // bq, s // bk),
        in_specs=_indexer_specs(j, c, bq, bk),
        out_specs=pl.BlockSpec((None, bq, bk), lambda b, i, kb: (b, i, kb)),
        out_shape=jax.ShapeDtypeStruct((b, t, s), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="dsa_indexer",
        interpret=interpret,
    )(q, _lanes(w.astype(_F32)), k)


def indexer_back_pallas(q, w, k, g, offset: int, bq: int, bk: int,
                        interpret: bool = False):
    """(dq, dw, dk) of ``indexer_scores`` for the scores' gradient ``g``:
    the queries' and weights' gradients summed over a query block's key
    blocks in VMEM, the keys' written per query block and summed after."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    width = q.shape[-1]
    q, k = _pad_lanes(q), _pad_lanes(k)
    (b, j, t, c), s = q.shape, k.shape[1]
    nq = t // bq
    dq, dw, dk = pl.pallas_call(
        functools.partial(_indexer_back_kernel, bq=bq, bk=bk, offset=offset),
        grid=(b, nq, s // bk),
        in_specs=_indexer_specs(j, c, bq, bk) + [
            pl.BlockSpec((None, bq, bk), lambda b, i, kb: (b, i, kb))],
        out_specs=[
            pl.BlockSpec((None, j, bq, c), lambda b, i, kb: (b, 0, i, 0)),
            pl.BlockSpec((None, j, bq, LANES),
                         lambda b, i, kb: (b, 0, i, 0)),
            pl.BlockSpec((None, None, bk, c), lambda b, i, kb: (b, i, kb, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, j, t, c), _F32),
                   jax.ShapeDtypeStruct((b, j, t, LANES), _F32),
                   jax.ShapeDtypeStruct((b, nq, s, c), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="dsa_indexer_back",
        interpret=interpret,
    )(q, _lanes(w.astype(_F32)), k, g)
    return dq[..., :width], dw[..., 0], dk.sum(axis=1)[..., :width]


def _indexer_tiles(q, k, bq, bk):
    return tiles(q.shape[2], k.shape[1], bq, bk, LANES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def indexer_scores(q, w, k, offset: int, bq: int, bk: int):
    """I (B, T, S) float32 = sum_j w[b,j,t] relu(q[b,j,t] . k[b,s]): ``q``
    (B, J, T, c), ``w`` (B, J, T), ``k`` (B, S, c). Key blocks past a query
    block's last visible key (from ``offset``) may read zero."""
    if not _indexer_tiles(q, k, bq, bk):
        return indexer_reference(q, w, k)
    return jax.lax.platform_dependent(
        q, w, k, default=indexer_reference,
        tpu=functools.partial(indexer_pallas, offset=offset, bq=bq, bk=bk))


def _indexer_fwd(q, w, k, offset, bq, bk):
    return indexer_scores(q, w, k, offset, bq, bk), (q, w, k)


def _indexer_bwd(offset, bq, bk, res, g):
    q, w, k = res

    def reference(q, w, k, g):
        return jax.vjp(indexer_reference, q, w, k)[1](g)

    def kernel(q, w, k, g):
        return indexer_back_pallas(q, w, k, g, offset, bq, bk)

    def typed(fn):
        return lambda *args: tuple(
            x.astype(a.dtype) for x, a in zip(fn(*args), (q, w, k)))

    with jax.named_scope("dsa_indexer"):
        if not _indexer_tiles(q, k, bq, bk):
            return typed(reference)(q, w, k, g)
        return jax.lax.platform_dependent(
            q, w, k, g, default=typed(reference), tpu=typed(kernel))


indexer_scores.defvjp(_indexer_fwd, _indexer_bwd)


# ---------------------------------------------------------------------------
# the threshold


def kth_largest_reference(x, k, most: int):
    """(each row's ``k``-th largest value, the index of the last key kept
    at it: the last key where every key at it is kept) for ``x`` (B, T, S)
    and ``k`` (B, T), 1 <= k <= most: the keys above the value and the
    earliest of those equal to it, ``k`` in all, are a row's ``lax.top_k``,
    which keeps the earlier of equal values."""
    top = jax.lax.top_k(x, most)[0]
    value = jnp.take_along_axis(top, k[..., None] - 1, axis=-1)
    need = k - jnp.sum(x > value, axis=-1)
    at = jnp.arange(x.shape[-1])
    earliest = -jax.lax.top_k(jnp.where(x == value, -at, -x.shape[-1]),
                              most)[0]
    cut = jnp.take_along_axis(earliest, need[..., None] - 1, axis=-1)[..., 0]
    # a row that keeps every key at its value: the last key
    cut = jnp.where(jnp.sum(x == value, axis=-1) > need, cut,
                    x.shape[-1] - 1)
    return value[..., 0], cut


_SIGN = 0x7FFFFFFF
# bits of a key's index that the tie's bisection visits: keys < 2^15
_INDEX_BITS = 15


def _order_key(bits):
    """Float32 bits as int32 that order as the floats do."""
    return jnp.where(bits < 0, bits ^ _SIGN, bits)


def _select_kernel(x_ref, k_ref, value_ref, cut_ref, keys):
    from jax.experimental import pallas as pl
    rows, width = keys.shape
    keys[...] = _order_key(jax.lax.bitcast_convert_type(x_ref[...],
                                                        jnp.int32))
    want = k_ref[...]

    def count(where):
        return jnp.sum(where.astype(jnp.int32), axis=1)[:, None]

    def enough(candidate):
        # rows whose count at or above ``candidate`` reaches their k
        return count(keys[...] >= _cols(candidate, width)) >= want

    zero = jnp.zeros((rows, LANES), jnp.int32)
    found = jnp.where(enough(zero), zero,
                      jnp.full((rows, LANES), -2**31, jnp.int32))

    def bit(i, found):
        candidate = found + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(enough(candidate), candidate, found)

    found = jax.lax.fori_loop(0, 31, bit, found)
    value_ref[...] = jax.lax.bitcast_convert_type(_order_key(found), _F32)
    # ties at the value: of the keys equal to it the earliest ``need`` are
    # kept, the last of them at ``cut`` (the last key where none is over)
    tied = keys[...] == _cols(found, width)
    need = want - count(keys[...] > _cols(found, width))
    over = count(tied) > need
    last = jnp.full((rows, LANES), width - 1, jnp.int32)
    cut_ref[...] = last

    @pl.when(jnp.any(over))
    def _():
        at = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

        def covers(candidate):
            # rows whose tied keys at or before ``candidate`` reach need
            return count(tied & (at <= _cols(candidate, width))) >= need

        def index_bit(i, found):
            # the least index that covers: grow from 0 the bits that do not
            candidate = found + jnp.left_shift(jnp.int32(1),
                                               _INDEX_BITS - 1 - i)
            return jnp.where(covers(candidate - 1), found, candidate)

        cut_ref[...] = jnp.where(over, jax.lax.fori_loop(
            0, _INDEX_BITS, index_bit, jnp.zeros((rows, LANES), jnp.int32)),
            last)


def kth_largest_pallas(x, k, rows: int = SELECT_ROWS,
                       interpret: bool = False):
    """``kth_largest`` as a bisection over the values' order keys: from the
    top bit down, keep a bit where the row still holds k values at or above
    what is kept so far; 32 counts of the row, which stays in VMEM. Where a
    block's rows hold more keys at their value than they keep, a second
    bisection over the keys' indices finds each row's cut."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, t, s = x.shape
    assert s <= 2**_INDEX_BITS, s
    row = pl.BlockSpec((None, rows, LANES), lambda b, i: (b, i, 0))
    value, cut = pl.pallas_call(
        _select_kernel,
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((None, rows, s), lambda b, i: (b, i, 0)), row],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((b, t, LANES), _F32),
                   jax.ShapeDtypeStruct((b, t, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="dsa_select",
        interpret=interpret,
    )(x, _lanes(k.astype(jnp.int32)))
    return value[..., 0], cut[..., 0]


def kth_largest(x, k, most: int):
    """Each row's ``k``-th largest value and the index of the last key
    kept at it: ``x`` (B, T, S) float32, ``k`` (B, T) int32 with 1 <= k <=
    ``most``. A row's ``k`` chosen keys are those over the value and those
    equal to it at or before the cut: ``lax.top_k``'s, the earlier of equal
    values first. No gradient."""
    x, k = jax.lax.stop_gradient(x), jax.lax.stop_gradient(k)
    reference = functools.partial(kth_largest_reference, most=most)
    if x.shape[1] % SELECT_ROWS or x.shape[2] % LANES:
        return reference(x, k)
    return jax.lax.platform_dependent(x, k, default=reference,
                                      tpu=kth_largest_pallas)


# ---------------------------------------------------------------------------
# attention over the chosen keys


def attention_reference(q, k, v, d, scale: float):
    """(o (B, H, T, e) in q's dtype, lse (B, H, T) float32) over whole
    arrays."""
    b, h, t, e = q.shape
    g = k.shape[1]
    s = jnp.einsum("bgnte,bgse->bgnts", q.reshape(b, g, h // g, t, e), k,
                   preferred_element_type=_F32) * scale
    chosen = (d >= 0)[:, None, None]
    s = jnp.where(chosen, s, MASK)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(chosen, jnp.exp(s - lse[..., None]), 0.0)
    o = jnp.einsum("bgnts,bgse->bgnte", p.astype(v.dtype), v,
                   preferred_element_type=_F32)
    return o.reshape(b, h, t, e).astype(q.dtype), lse.reshape(b, h, t)


def blocks_live(d, bq: int, bk: int):
    """(B, T / bq, S / bk) bool: the blocks of (query, key) pairs that hold
    a chosen pair."""
    b, t, s = d.shape
    return jnp.any(d.reshape(b, t // bq, bq, s // bk, bk) >= 0, axis=(2, 4))


def _attn_kernel(live_ref, q_ref, k_ref, v_ref, d_ref, o_ref, lse_ref, m_sc,
                 l_sc, acc_sc, *, scale, nq, nk):
    from jax.experimental import pallas as pl
    b, qi, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n, bq, e = q_ref.shape
    bk = k_ref.shape[0]

    @pl.when(kb == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, _F32)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(live_ref[(b * nq + qi) * nk + kb] != 0)
    def _():
        k, v = k_ref[...], v_ref[...]
        chosen = d_ref[...] >= 0
        for i in range(n):
            s = jax.lax.dot_general(q_ref[i], k, _TRANS_B,
                                    preferred_element_type=_F32) * scale
            s = jnp.where(chosen, s, MASK)
            m_prev = m_sc[i]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            p = jnp.exp(s - _cols(m_next, bk))
            alpha = jnp.exp(m_prev - m_next)
            l_sc[i] = alpha * l_sc[i] + jnp.sum(p, axis=1)[:, None]
            m_sc[i] = m_next
            acc_sc[i] = acc_sc[i] * _cols(alpha, e) + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=_F32)

    @pl.when(kb == nk - 1)
    def _():
        for i in range(n):
            o_ref[i] = (acc_sc[i] / _cols(l_sc[i], e)).astype(o_ref.dtype)
            lse_ref[i] = m_sc[i] + jnp.log(l_sc[i])


def _attn_specs(n, bq, bk, e, kv_major: bool):
    """Block specs of (q, k, v, d, and per-query rows) over a grid of
    (B, G, query blocks, key blocks), or of (B, G, key blocks, query
    blocks) where ``kv_major``."""
    from jax.experimental import pallas as pl
    if kv_major:
        def qk(f):
            return lambda b, g, kb, i, live: f(b, g, i, kb)
    else:
        def qk(f):
            return lambda b, g, i, kb, live: f(b, g, i, kb)
    rows = pl.BlockSpec((None, n, bq, e), qk(lambda b, g, i, kb: (b, g, i, 0)))
    lanes = pl.BlockSpec((None, n, bq, LANES),
                         qk(lambda b, g, i, kb: (b, g, i, 0)))
    keys = pl.BlockSpec((None, None, bk, e),
                        qk(lambda b, g, i, kb: (b, g, kb, 0)))
    mask = pl.BlockSpec((None, bq, bk), qk(lambda b, g, i, kb: (b, i, kb)))
    return rows, lanes, keys, mask


def _attn_call(kernel, name, grid, in_specs, out_specs, out_shape,
               interpret, scratch=(), kv_major=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name,
        interpret=interpret,
    )


def sparse_attention_pallas(q, k, v, d, scale: float, bq: int, bk: int,
                            interpret: bool = False):
    """The kernel of ``sparse_attention``'s forward: a grid of (B, G, query
    blocks, key blocks), the key blocks innermost; a step takes the n query
    heads that read one key/value head, each head's scores of the block
    in turn, and keeps each head's running max, sum and output in VMEM."""
    from jax.experimental.pallas import tpu as pltpu
    (b, h, t, e), (g, s) = q.shape, k.shape[1:3]
    n, nq, nk = h // g, t // bq, s // bk
    rows, lanes, keys, mask = _attn_specs(n, bq, bk, e, False)
    o, lse = _attn_call(
        functools.partial(_attn_kernel, scale=scale, nq=nq, nk=nk),
        "sparse_attn", (b, g, nq, nk), [rows, keys, keys, mask],
        [rows, lanes],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, h, t, LANES), _F32)], interpret,
        scratch=[pltpu.VMEM((n, bq, LANES), _F32),
                 pltpu.VMEM((n, bq, LANES), _F32),
                 pltpu.VMEM((n, bq, e), _F32)],
    )(blocks_live(d, bq, bk).reshape(-1).astype(jnp.int32), q, k, v, d)
    return o, lse[..., 0]


def _probs(q, k, lse_i, chosen, scale, bk):
    s = jax.lax.dot_general(q, k, _TRANS_B, preferred_element_type=_F32)
    return jnp.where(chosen, jnp.exp(s * scale - _cols(lse_i, bk)), 0.0)


def _attn_dq_kernel(live_ref, q_ref, k_ref, v_ref, d_ref, do_ref, lse_ref,
                    dl_ref, dq_ref, *, scale, nq, nk):
    from jax.experimental import pallas as pl
    b, qi, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bk = k_ref.shape[0]

    @pl.when(kb == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(live_ref[(b * nq + qi) * nk + kb] != 0)
    def _():
        k, v = k_ref[...], v_ref[...]
        chosen = d_ref[...] >= 0
        for i in range(q_ref.shape[0]):
            p = _probs(q_ref[i], k, lse_ref[i], chosen, scale, bk)
            dp = jax.lax.dot_general(do_ref[i], v, _TRANS_B,
                                     preferred_element_type=_F32)
            ds = p * (dp - _cols(dl_ref[i], bk))
            dq_ref[i] += scale * jax.lax.dot(
                ds.astype(k.dtype), k, preferred_element_type=_F32)


def _attn_dkv_kernel(live_ref, q_ref, k_ref, v_ref, d_ref, do_ref, lse_ref,
                     dl_ref, dk_ref, dv_ref, *, scale, nq, nk):
    from jax.experimental import pallas as pl
    b, kb, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bk = k_ref.shape[0]

    @pl.when(qi == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(live_ref[(b * nq + qi) * nk + kb] != 0)
    def _():
        k, v = k_ref[...], v_ref[...]
        chosen = d_ref[...] >= 0
        dk = jnp.zeros(dk_ref.shape, _F32)
        dv = jnp.zeros(dv_ref.shape, _F32)
        for i in range(q_ref.shape[0]):
            q, do = q_ref[i], do_ref[i]
            p = _probs(q, k, lse_ref[i], chosen, scale, bk)
            dv = dv + jax.lax.dot(p.T.astype(do.dtype), do,
                                  preferred_element_type=_F32)
            dp = jax.lax.dot_general(do, v, _TRANS_B,
                                     preferred_element_type=_F32)
            ds = p * (dp - _cols(dl_ref[i], bk))
            dk = dk + jax.lax.dot(ds.T.astype(q.dtype), q,
                                  preferred_element_type=_F32)
        dk_ref[...] += scale * dk
        dv_ref[...] += dv


def sparse_attention_back_pallas(q, k, v, d, do, lse, dl, scale: float,
                                 bq: int, bk: int, interpret: bool = False):
    """(dq, dk, dv) float32 of ``sparse_attention`` for the output's gradient
    ``do`` and ``dl`` = rowsum(do * o) less the log-sum-exp's gradient, per
    (head, query): the queries' gradient summed over key blocks (query
    blocks outer), the keys' and values' over query blocks and the n heads
    that read them (key blocks outer)."""
    (b, h, t, e), (g, s) = q.shape, k.shape[1:3]
    n, nq, nk = h // g, t // bq, s // bk
    live = blocks_live(d, bq, bk).reshape(-1).astype(jnp.int32)
    args = (live, q, k, v, d, do, _lanes(lse), _lanes(dl))
    kernel_args = dict(scale=scale, nq=nq, nk=nk)
    rows, lanes, keys, mask = _attn_specs(n, bq, bk, e, False)
    (dq,) = _attn_call(
        functools.partial(_attn_dq_kernel, **kernel_args), "sparse_attn_dq",
        (b, g, nq, nk), [rows, keys, keys, mask, rows, lanes, lanes], [rows],
        [jax.ShapeDtypeStruct(q.shape, _F32)], interpret)(*args)
    rows, lanes, keys, mask = _attn_specs(n, bq, bk, e, True)
    dk, dv = _attn_call(
        functools.partial(_attn_dkv_kernel, **kernel_args),
        "sparse_attn_dkv", (b, g, nk, nq),
        [rows, keys, keys, mask, rows, lanes, lanes], [keys, keys],
        [jax.ShapeDtypeStruct(k.shape, _F32)] * 2, interpret)(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def sparse_attention(q, k, v, d, scale: float, bq: int, bk: int):
    """Attention of each query over the keys ``d`` chooses (``d >= 0``):
    (o (B, H, T, e) in q's dtype, lse (B, H, T) float32). Products take the
    operands' dtype and accumulate in float32; the softmax is float32. Every
    query must choose a key. ``d`` gets no gradient."""
    if not tiles(q.shape[2], k.shape[2], bq, bk, q.shape[3]):
        return attention_reference(q, k, v, d, scale)
    return jax.lax.platform_dependent(
        q, k, v, d, default=functools.partial(attention_reference,
                                              scale=scale),
        tpu=functools.partial(sparse_attention_pallas, scale=scale, bq=bq,
                              bk=bk))


def _attention_fwd(q, k, v, d, scale, bq, bk):
    """The forward under a gradient: the output and the log-sum-exp (B, H,
    T), which the backward reads, named here on the residuals themselves,
    so that a remat policy that keeps ``OUT`` and ``LSE`` hands the backward
    these arrays and the forward kernel does not run again in it (a name on
    the values the caller receives would not reach the residuals)."""
    o, lse = sparse_attention(q, k, v, d, scale, bq, bk)
    o, lse = checkpoint_name(o, OUT), checkpoint_name(lse, LSE)
    return (o, lse), (q, k, v, d, o, lse)


def _attention_bwd(scale, bq, bk, res, cot):
    q, k, v, d, o, lse = res
    do, dlse = cot
    dl = jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1) - dlse

    def reference(q, k, v, d, do, lse, dl):
        del lse, dl
        _, back = jax.vjp(lambda q, k, v: attention_reference(
            q, k, v, d, scale), q, k, v)
        return back((do, dlse))

    def kernel(q, k, v, d, do, lse, dl):
        return sparse_attention_back_pallas(q, k, v, d, do, lse, dl, scale,
                                            bq, bk)

    def typed(fn):
        return lambda *args: tuple(
            x.astype(a.dtype) for x, a in zip(fn(*args), (q, k, v)))

    with jax.named_scope("dsa_attn"):
        if not tiles(q.shape[2], k.shape[2], bq, bk, q.shape[3]):
            dq, dk, dv = typed(reference)(q, k, v, d, do, lse, dl)
        else:
            dq, dk, dv = jax.lax.platform_dependent(
                q, k, v, d, do, lse, dl, default=typed(reference),
                tpu=typed(kernel))
    return dq, dk, dv, jnp.zeros_like(d)


sparse_attention.defvjp(_attention_fwd, _attention_bwd)


# ---------------------------------------------------------------------------
# the heads' attention per (query, key), for the indexer's loss


def chosen_probs_reference(q, k, lse, d, scale: float):
    b, h, t, e = q.shape
    g = k.shape[1]
    s = jnp.einsum("bgnte,bgse->bgnts", q.reshape(b, g, h // g, t, e), k,
                   preferred_element_type=_F32) * scale
    p = jnp.exp(s - lse.reshape(b, g, h // g, t)[..., None])
    return jnp.where(d >= 0, p.mean(axis=(1, 2)), 0.0)


def _probs_kernel(live_ref, q_ref, k_ref, lse_ref, d_ref, p_ref, *, scale,
                  heads, nq, nk):
    from jax.experimental import pallas as pl
    b, qi, kb, g = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    bk = k_ref.shape[0]

    @pl.when(g == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(live_ref[(b * nq + qi) * nk + kb] != 0)
    def _():
        k = k_ref[...]
        chosen = d_ref[...] >= 0
        total = jnp.zeros(p_ref.shape, _F32)
        for i in range(q_ref.shape[0]):
            total = total + _probs(q_ref[i], k, lse_ref[i], chosen, scale,
                                   bk)
        p_ref[...] += total * (1.0 / heads)


def chosen_probs_pallas(q, k, lse, d, scale: float, bq: int, bk: int,
                        interpret: bool = False):
    """The kernel of ``chosen_probs``: a grid of (B, query blocks, key
    blocks, G), the key/value heads innermost, each adding its n query
    heads' probabilities to the block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (b, h, t, e), (g, s) = q.shape, k.shape[1:3]
    n, nq, nk = h // g, t // bq, s // bk
    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, heads=h, nq=nq, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nq, nk, g),
            in_specs=[
                pl.BlockSpec((None, n, bq, e),
                             lambda b, i, kb, g, live: (b, g, i, 0)),
                pl.BlockSpec((None, None, bk, e),
                             lambda b, i, kb, g, live: (b, g, kb, 0)),
                pl.BlockSpec((None, n, bq, LANES),
                             lambda b, i, kb, g, live: (b, g, i, 0)),
                pl.BlockSpec((None, bq, bk),
                             lambda b, i, kb, g, live: (b, i, kb))],
            out_specs=pl.BlockSpec((None, bq, bk),
                                   lambda b, i, kb, g, live: (b, i, kb))),
        out_shape=jax.ShapeDtypeStruct((b, t, s), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="dsa_probs",
        interpret=interpret,
    )(blocks_live(d, bq, bk).reshape(-1).astype(jnp.int32), q, k,
      _lanes(lse), d)


def chosen_probs(q, k, lse, d, scale: float, bq: int, bk: int):
    """P (B, T, S) float32: the mean over the H query heads of each head's
    attention probability exp(score - lse) of (query, key), zero where
    ``d`` chose no key: each row sums to one. No gradient."""
    q, k, lse, d = (jax.lax.stop_gradient(x) for x in (q, k, lse, d))
    reference = functools.partial(chosen_probs_reference, scale=scale)
    if not tiles(q.shape[2], k.shape[2], bq, bk, q.shape[3]):
        return reference(q, k, lse, d)
    return jax.lax.platform_dependent(
        q, k, lse, d, default=reference,
        tpu=functools.partial(chosen_probs_pallas, scale=scale, bq=bq,
                              bk=bk))
