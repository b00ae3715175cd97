"""Kimi Delta Attention's recurrence (the gated delta rule with a decay for
each key channel; the Kimi Linear technical report, arXiv:2510.26692) as a
chunked scan: a Pallas kernel for its forward and one for its backward.

Per sequence and head, with a state S of d_k x d_v float32 carried from one
position to the next::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` (d_k) are log-decays (at most 0) and ``beta_t`` a scalar in (0, 1);
the caller folds any scale into q. Shapes: q, k (B, H, T, d_k), v (B, H, T,
d_v), g (B, H, T, d_k), beta (B, H, T), S (B, H, d_k, d_v).

The chunked form (exact algebra, no approximation): within a chunk of C
positions with G_t the cumulative sum of g from the chunk's start and S_0
the state it starts from, write u_t = beta_t (v_t - (Diag(exp(g_t))
S_{t-1})^T k_t), so that S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T. Then

    (I + A) U = beta (V - (K * exp(G)) S_0),  A[t,i] = beta_t sum_c k_t,c
                k_i,c exp(G_t,c - G_i,c) for i < t
    O = (Q * exp(G)) S_0 + M U,               M[t,i] = sum_c q_t,c k_i,c
                exp(G_t,c - G_i,c) for i <= t
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

Every exponent is a difference G_t - G_i with i <= t inside one chunk, so
none exceeds zero and none can overflow, however fast a channel decays. The
pairs (t, i) are taken in blocks of ``BLOCK`` rows: where i lies in an
earlier block than t, exp(G_t - G_i) = exp(G_t - G_r) exp(G_r - G_i) with r
the first row of t's block, both factors at most one, and the block is a
matrix product; inside a block, column by column. U is found block by block
by forward substitution (the inverse of I + A by a series would cancel
catastrophically where beta is near one and keys align).

On a TPU the scan is ``kda_scan``'s two kernels over a grid of (B, H,
chunks), the state in VMEM from one chunk to the next, each kernel summing
a chunk's log-decays into G and folding beta into k and v itself (beta
enters by chunk, (B, H, chunks, C)): the forward writes
each chunk's output and the state each chunk starts from; the backward runs
over the chunks in reverse from those states, the state's gradient in VMEM,
recomputing a chunk's A, M and U from its start state. Products inside the
kernels take float32 operands at full precision. Elsewhere (the CPU tests,
the rehearsal, the actor's one-step window) it is ``recurrent``, one step a
position, which the tests hold the kernels to in interpret mode.
"""

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
LANES = 128
# rows of a block of (t, i) pairs, whole sublane tiles of float32: a block's
# pairs with earlier blocks are matrix products of this many rows, its pairs
# inside it a column at a time
BLOCK = 32
_VMEM_BYTES = 64 * 2**20
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_F32)


def tiles(d_k: int, d_v: int, chunk: int) -> bool:
    """Whether the kernels tile these widths: heads of whole lanes, chunks
    of whole blocks."""
    return d_k % LANES == 0 and d_v % LANES == 0 and chunk % _rows(chunk) == 0


def _rows(size: int) -> int:
    """Rows of a block of (t, i) pairs in a chunk of ``size`` positions."""
    return min(BLOCK, size)


# ---------------------------------------------------------------------------
# one position at a time


def recurrent(q, k, v, g, beta, s0):
    """(o (B, H, T, d_v), S (B, H, d_k, d_v)) float32 by the recurrence, one
    ``lax.scan`` step a position."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        told = jnp.einsum("bhd,bhdv->bhv", k_t, s, precision=_HIGHEST)
        s = s + (b_t[..., None, None] * k_t[..., :, None]
                 * (v_t - told)[..., None, :])
        return s, jnp.einsum("bhd,bhdv->bhv", q_t, s, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(_F32), 2, 0) for x in (q, k, v, g, beta))
    s, o = jax.lax.scan(step, s0.astype(_F32), xs)
    return jnp.moveaxis(o, 0, 2), s


# ---------------------------------------------------------------------------
# the kernels


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _diagonal(x):
    """A (1, n) row as an (n, 1) column, or a column as a row: through the
    diagonal of its (n, n) broadcast (no transpose of a vector)."""
    n = max(x.shape)
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    full = jnp.where(eye, jnp.broadcast_to(x, (n, n)), 0.0)
    return jnp.sum(full, axis=1 if x.shape[0] == 1 else 0,
                   keepdims=True)


def _block_pairs(q, k, kb, g, j, block):
    """Block ``j``'s rows of A and M over the columns of earlier blocks, as
    the factors the products take: (kb * down, q * down, right, up), so that
    A's rows are ``(kb * down) right^T`` and ``right`` is zero from row r
    on (r the block's first row)."""
    size = g.shape[0]
    r = j * block
    sl = slice(r, r + block)
    down = jnp.exp(g[sl] - g[r:r + 1])
    up = jnp.exp(jnp.where(_iota((size, 1), 0) < r, g[r:r + 1] - g,
                           -jnp.inf))
    return kb[sl] * down, q[sl] * down, k * up, up, down


def _diagonal_decay(g, j, i, block):
    """exp(G_t - G_ci) for the rows t of block ``j`` (zero above the
    diagonal), ci = the block's ``i``-th row."""
    r = j * block
    ci = r + i
    return jnp.exp(jnp.where(_iota((block, 1), 0) >= i,
                             g[r:r + block] - g[ci:ci + 1], -jnp.inf))


def _chunk_forward(q, k, kb, vb, g, s0, u_sc, a_sc=None, m_sc=None):
    """One chunk's U into ``u_sc`` and its output, block by block; where
    ``a_sc`` and ``m_sc`` are given (the backward), A and M into them
    instead of the output."""
    size = g.shape[0]
    block = _rows(size)
    sub, lane = _iota((block, 1), 0), _iota((block, size), 1)
    eg = jnp.exp(g)
    u_sc[...] = jnp.zeros_like(u_sc)
    out = []
    for j in range(size // block):
        r = j * block
        sl = slice(r, r + block)
        y_j = vb[sl] - _dot(kb[sl] * eg[sl], s0)
        if a_sc is None:
            o_j = _dot(q[sl] * eg[sl], s0)
        if r:
            kb_d, q_d, right, _, _ = _block_pairs(q, k, kb, g, j, block)
            a_j, m_j = _dot(kb_d, right, _NT), _dot(q_d, right, _NT)
            before = u_sc[...]
            y_j = y_j - _dot(a_j, before)
            if a_sc is None:
                o_j = o_j + _dot(m_j, before)
        else:
            a_j = m_j = jnp.zeros((block, size), _F32)
        u_j = jnp.zeros_like(y_j)
        for i in range(block):
            ci = r + i
            kc = k[ci:ci + 1] * _diagonal_decay(g, j, i, block)
            col_a = jnp.where(sub > i, jnp.sum(kb[sl] * kc, axis=-1,
                                               keepdims=True), 0.0)
            col_m = jnp.sum(q[sl] * kc, axis=-1, keepdims=True)
            u_i = y_j[i:i + 1]
            y_j = y_j - col_a * u_i
            u_j = jnp.where(sub == i, u_i, u_j)
            if a_sc is None:
                o_j = o_j + col_m * u_i
            else:
                a_j = a_j + jnp.where(lane == ci, col_a, 0.0)
                m_j = m_j + jnp.where(lane == ci, col_m, 0.0)
        u_sc[sl] = u_j
        if a_sc is None:
            out.append(o_j)
        else:
            a_sc[sl] = a_j
            m_sc[sl] = m_j
    return out


def _chunk_inputs(q_ref, k_ref, v_ref, g_ref, beta_ref, at):
    """A chunk's q, k, v, its beta as a column (C, 1) and its cumulative
    log-decays G, float32, and the lower triangle of ones that sums g into
    G; ``at`` the chunk's index."""
    from jax.experimental import pallas as pl
    q, k, v, g = (r[...].astype(_F32) for r in (q_ref, k_ref, v_ref, g_ref))
    size = g.shape[0]
    beta = _diagonal(beta_ref[pl.ds(at, 1), :])
    lower = (_iota((size, size), 0) >= _iota((size, size), 1)).astype(_F32)
    return q, k, v, beta, _dot(lower, g), lower


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref,
                    s_ref, st_ref, s_sc, u_sc):
    from jax.experimental import pallas as pl
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_sc[...] = s0_ref[...]

    s0 = s_sc[...]
    st_ref[...] = s0
    q, k, v, beta, g, _ = _chunk_inputs(q_ref, k_ref, v_ref, g_ref, beta_ref,
                                        c)
    kb, vb = k * beta, v * beta
    size = g.shape[0]
    block = _rows(size)
    for j, o_j in enumerate(_chunk_forward(q, k, kb, vb, g, s0, u_sc)):
        o_ref[j * block:(j + 1) * block] = o_j.astype(o_ref.dtype)
    last = g[size - 1:size]
    s_new = (s0 * _diagonal(jnp.exp(last))
             + _dot(k * jnp.exp(last - g), u_sc[...], _TN))
    s_sc[...] = s_new
    s_ref[...] = s_new


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, do_ref,
                     dsf_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                     ds0_ref, ds_sc, u_sc, a_sc, m_sc, dy_sc, *, chunks):
    from jax.experimental import pallas as pl
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        ds_sc[...] = dsf_ref[...]

    ds, s0 = ds_sc[...], st_ref[...]
    at = chunks - 1 - c
    q, k, v, beta, g, lower = _chunk_inputs(q_ref, k_ref, v_ref, g_ref,
                                            beta_ref, at)
    kb, vb = k * beta, v * beta
    do = do_ref[...].astype(_F32)
    size = g.shape[0]
    block = _rows(size)
    sub, lane = _iota((block, 1), 0), _iota((block, size), 1)
    _chunk_forward(q, k, kb, vb, g, s0, u_sc, a_sc, m_sc)
    a, m, u = a_sc[...], m_sc[...], u_sc[...]
    eg = jnp.exp(g)
    last = g[size - 1:size]
    dec = jnp.exp(last - g)
    kr = k * dec

    # (I + A)^T dY = dU, block by block from the last
    du = _dot(m, do, _TN) + _dot(kr, ds)
    dy_sc[...] = jnp.zeros_like(dy_sc)
    for j in reversed(range(size // block)):
        r = j * block
        sl = slice(r, r + block)
        rest = du[sl] - _dot(a, dy_sc[...], _TN)[sl]
        a_j = a[sl]
        dy_j = jnp.zeros_like(rest)
        for i in reversed(range(block)):
            col = jnp.sum(jnp.where(lane == r + i, a_j, 0.0), axis=-1,
                          keepdims=True)
            dy_i = rest[i:i + 1] - jnp.sum(col * dy_j, axis=0, keepdims=True)
            dy_j = jnp.where(sub == i, dy_i, dy_j)
        dy_sc[sl] = dy_j
    dy = dy_sc[...]

    rows, cols = _iota((size, size), 0), _iota((size, size), 1)
    da = jnp.where(rows > cols, -_dot(dy, u, _NT), 0.0)
    dm = jnp.where(rows >= cols, _dot(do, u, _NT), 0.0)
    dqg = _dot(do, s0, _NT)
    dkbg = -_dot(dy, s0, _NT)
    dkr = _dot(u, ds, _NT)
    qg, kbg = q * eg, kb * eg

    # the pairs' part of the gradients of q, kb and k
    dq_p, dkb_p, dk_d = [], [], []
    dk_p = jnp.zeros_like(k)
    for j in range(size // block):
        r = j * block
        sl = slice(r, r + block)
        dkb_j = dq_j = jnp.zeros((block, k.shape[1]), _F32)
        if r:
            kb_d, q_d, right, up, down = _block_pairs(q, k, kb, g, j,
                                                      block)
            earlier = lane < r
            da_j = jnp.where(earlier, da[sl], 0.0)
            dm_j = jnp.where(earlier, dm[sl], 0.0)
            dkb_j = _dot(da_j, right) * down
            dq_j = _dot(dm_j, right) * down
            dk_p = dk_p + (_dot(da_j, kb_d, _TN)
                           + _dot(dm_j, q_d, _TN)) * up
        dk_j = jnp.zeros_like(dkb_j)
        for i in range(block):
            ci = r + i
            e = _diagonal_decay(g, j, i, block)
            hit = lane == ci
            col_a = jnp.sum(jnp.where(hit, da[sl], 0.0), axis=-1,
                            keepdims=True)
            col_m = jnp.sum(jnp.where(hit, dm[sl], 0.0), axis=-1,
                            keepdims=True)
            kc = k[ci:ci + 1] * e
            dkb_j = dkb_j + col_a * kc
            dq_j = dq_j + col_m * kc
            dk_i = jnp.sum((col_a * kb[sl] + col_m * q[sl]) * e, axis=0,
                           keepdims=True)
            dk_j = jnp.where(sub == i, dk_i, dk_j)
        dq_p.append(dq_j)
        dkb_p.append(dkb_j)
        dk_d.append(dk_j)
    dq_p = jnp.concatenate(dq_p, axis=0)
    dkb_p = jnp.concatenate(dkb_p, axis=0)
    dk_p = dk_p + jnp.concatenate(dk_d, axis=0)

    dg = (dqg * qg + dkbg * kbg - dkr * kr
          + kb * dkb_p + q * dq_p - k * dk_p)
    held = jnp.exp(last) * _diagonal(jnp.sum(s0 * ds, axis=-1,
                                             keepdims=True))
    dg = dg + jnp.where(_iota((size, 1), 0) == size - 1,
                        jnp.sum(dkr * kr, axis=0, keepdims=True) + held, 0.0)
    dkb = dkbg * eg + dkb_p
    dq_ref[...] = (dqg * eg + dq_p).astype(dq_ref.dtype)
    dk_ref[...] = (dkr * dec + dk_p + dkb * beta).astype(dk_ref.dtype)
    dv_ref[...] = (dy * beta).astype(dv_ref.dtype)
    # G is the cumulative sum of g: g's gradient sums G's from t on
    dg_ref[...] = _dot(lower, dg, _TN)
    dbeta_ref[pl.ds(at, 1), :] = _diagonal(
        jnp.sum(dkb * k + dy * v, axis=-1, keepdims=True))
    ds0 = (_dot(qg, do, _TN) - _dot(kbg, dy, _TN)
           + ds * _diagonal(jnp.exp(last)))
    ds_sc[...] = ds0
    ds0_ref[...] = ds0


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name, interpret=interpret)


def _tile_specs(chunk, width, n, reverse=False):
    from jax.experimental import pallas as pl
    at = (lambda b, h, c: (b, h, n - 1 - c, 0)) if reverse else \
        (lambda b, h, c: (b, h, c, 0))
    return pl.BlockSpec((None, None, chunk, width), at)


def _whole_spec(rows, cols):
    """A (rows, cols) block of each sequence and head, the same at every
    chunk (a state, or beta by chunk)."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((None, None, rows, cols), lambda b, h, c: (b, h, 0, 0))


def _chunk_states_spec(d_k, d_v, n, reverse=False):
    from jax.experimental import pallas as pl
    at = (lambda b, h, c: (b, h, n - 1 - c, 0, 0)) if reverse else \
        (lambda b, h, c: (b, h, c, 0, 0))
    return pl.BlockSpec((None, None, None, d_k, d_v), at)


def forward_pallas(q, k, v, g, beta, s0, interpret: bool = False):
    """The forward kernel over inputs of whole chunks: q, k, v (B, H, T,
    .), g (B, H, T, d_k) float32, beta (B, H, chunks, C) float32 and S_0.
    Returns (o (B, H, T, d_v), the last state, the state each chunk starts
    from (B, H, chunks, d_k, d_v)), all float32."""
    from jax.experimental.pallas import tpu as pltpu
    b, h, t, d_k = q.shape
    d_v, (n, chunk) = v.shape[-1], beta.shape[2:]
    keys, values = _tile_specs(chunk, d_k, n), _tile_specs(chunk, d_v, n)
    state = _whole_spec(d_k, d_v)
    return _call(
        _forward_kernel, "kda_scan_fwd", (b, h, n),
        [keys, keys, values, keys, _whole_spec(n, chunk), state],
        [values, state, _chunk_states_spec(d_k, d_v, n)],
        [jax.ShapeDtypeStruct((b, h, t, d_v), _F32),
         jax.ShapeDtypeStruct((b, h, d_k, d_v), _F32),
         jax.ShapeDtypeStruct((b, h, n, d_k, d_v), _F32)],
        [pltpu.VMEM((d_k, d_v), _F32), pltpu.VMEM((chunk, d_v), _F32)],
        interpret)(q, k, v, g, beta, s0)


def backward_pallas(q, k, v, g, beta, states, do, ds,
                    interpret: bool = False):
    """The backward kernel: the gradients of ``forward_pallas``'s inputs
    (q, k, v in their dtypes; g, beta and S_0 float32) for the output's
    gradient ``do`` and the last state's ``ds``."""
    from jax.experimental.pallas import tpu as pltpu
    b, h, t, d_k = q.shape
    d_v, (n, chunk) = v.shape[-1], beta.shape[2:]
    keys = _tile_specs(chunk, d_k, n, reverse=True)
    values = _tile_specs(chunk, d_v, n, reverse=True)
    state, by_chunk = _whole_spec(d_k, d_v), _whole_spec(n, chunk)
    return _call(
        functools.partial(_backward_kernel, chunks=n), "kda_scan_bwd",
        (b, h, n),
        [keys, keys, values, keys, by_chunk,
         _chunk_states_spec(d_k, d_v, n, reverse=True), values, state],
        [keys, keys, values, keys, by_chunk, state],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
        + [jax.ShapeDtypeStruct(g.shape, _F32),
           jax.ShapeDtypeStruct(beta.shape, _F32),
           jax.ShapeDtypeStruct((b, h, d_k, d_v), _F32)],
        [pltpu.VMEM((d_k, d_v), _F32), pltpu.VMEM((chunk, d_v), _F32),
         pltpu.VMEM((chunk, chunk), _F32), pltpu.VMEM((chunk, chunk), _F32),
         pltpu.VMEM((chunk, d_v), _F32)],
        interpret)(q, k, v, g, beta, states, do, ds)


# ---------------------------------------------------------------------------
# the scan


def _prepare(q, k, v, g, beta, chunk: int):
    """The kernels' inputs: T padded to whole chunks (zero keys and decays
    leave the state as it is), g float32, beta float32 by chunk (B, H,
    chunks, C)."""
    b, h, t, _ = q.shape
    pad = -t % chunk
    widen = [(0, 0), (0, 0), (0, pad), (0, 0)]
    beta = jnp.pad(beta.astype(_F32), widen[:3]).reshape(b, h, -1, chunk)
    return (jnp.pad(q, widen), jnp.pad(k, widen), jnp.pad(v, widen),
            jnp.pad(g.astype(_F32), widen), beta)


def chunked_forward(q, k, v, g, beta, s0, chunk: int, interpret=False):
    """(o (B, H, T, d_v), S, the chunks' start states) float32 by the
    forward kernel."""
    o, s, states = forward_pallas(*_prepare(q, k, v, g, beta, chunk),
                                  s0.astype(_F32), interpret)
    return o[:, :, :q.shape[2]], s, states


def chunked_backward(q, k, v, g, beta, states, do, ds, chunk: int,
                     interpret=False):
    """The gradients of (q, k, v, g, beta, S_0) by the backward kernel."""
    prepared, pull = jax.vjp(
        functools.partial(_prepare, chunk=chunk), q, k, v, g, beta)
    pad = prepared[0].shape[2] - q.shape[2]
    do = jnp.pad(do.astype(_F32), [(0, 0), (0, 0), (0, pad), (0, 0)])
    *grads, ds0 = backward_pallas(*prepared, states, do, ds.astype(_F32),
                                  interpret)
    return (*pull(tuple(grads)), ds0)


def _dispatch(q, k, v, chunk):
    """Whether the kernels may run: more than one position, and widths and
    chunk they tile."""
    return q.shape[2] > 1 and tiles(k.shape[-1], v.shape[-1], chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def kda_scan(q, k, v, g, beta, s0, chunk: int):
    """(o (B, H, T, d_v), S (B, H, d_k, d_v)) float32: the recurrence over
    T positions from S_0 in chunks of ``chunk``, by the kernels on a TPU
    where they tile, by ``recurrent`` elsewhere."""
    o, s, _ = _forward(q, k, v, g, beta, s0, chunk)
    return o, s


def _forward(q, k, v, g, beta, s0, chunk):
    if not _dispatch(q, k, v, chunk):
        return (*recurrent(q, k, v, g, beta, s0), None)
    b, h, t, d_k = q.shape
    n = -(-t // chunk)

    def plain(q, k, v, g, beta, s0):
        # the kernels' start states are read by their backward only
        return (*recurrent(q, k, v, g, beta, s0),
                jnp.zeros((b, h, n, d_k, v.shape[-1]), _F32))

    return jax.lax.platform_dependent(
        q, k, v, g, beta, s0, default=plain,
        tpu=functools.partial(chunked_forward, chunk=chunk))


def _scan_fwd(q, k, v, g, beta, s0, chunk):
    o, s, states = _forward(q, k, v, g, beta, s0, chunk)
    return (o, s), (q, k, v, g, beta, s0, states)


def _scan_bwd(chunk, res, cot):
    q, k, v, g, beta, s0, states = res
    do, ds = cot

    def reference(q, k, v, g, beta, s0, states, do, ds):
        del states
        _, back = jax.vjp(recurrent, q, k, v, g, beta, s0)
        return back((do, ds))

    def kernel(q, k, v, g, beta, s0, states, do, ds):
        del s0
        return chunked_backward(q, k, v, g, beta, states, do, ds, chunk)

    def typed(fn):
        return lambda *args: tuple(
            x.astype(a.dtype) for x, a in zip(fn(*args),
                                              (q, k, v, g, beta, s0)))

    args = (q, k, v, g, beta, s0, states, do, ds)
    with jax.named_scope("kda_scan"):
        if states is None:
            return typed(reference)(*args)
        return jax.lax.platform_dependent(*args, default=typed(reference),
                                          tpu=typed(kernel))


kda_scan.defvjp(_scan_fwd, _scan_bwd)
