"""Sequence (context) parallelism for the recurrent core.

The framework's long-context story (SURVEY §5.7): the reference bounds its
sequence dimension by config (55-step windows through a cuDNN LSTM,
/root/reference/model.py:33,103-108) and has no attention to ring over —
for a recurrence, the carry chain IS the sequence dependency. The
TPU-native equivalent of ring/all-to-all sequence parallelism is therefore
a **pipelined time-sharded scan**:

  * The window's time axis is chunked over the mesh's 'sp' axis — device k
    owns ``T/S`` contiguous steps of the input projection (the hoisted
    ``x @ Wi``, the bulk of the FLOPs, is embarrassingly parallel over
    time and never moves).
  * The batch axis is split into M microbatches, and the recurrent carry
    ``(c, h)`` — the ONLY cross-device tensor, ``2 * B_m * H`` floats —
    hops stage-to-stage over ICI via ``ppermute``, exactly once per
    microbatch per chunk boundary. Pipeline efficiency is M/(M+S-1).
  * The cell math is ``models.network.lstm_cell_step`` — the same function
    the in-chip scan uses — so the sharded unroll is the identical
    computation in the identical order: bit-exact against the single-device
    scan (asserted in tests/test_parallel.py).

When it wins: windows long enough that one chip's HBM cannot hold the
window's activations (T in the thousands — recurrent long-context
agents), or where per-chip serial latency dominates; chunking divides the
activation footprint by S at the cost of the (S-1)/(M+S-1) bubble. At the
reference's T=55, chunks of ~7 steps + carry hops LOSE to the single-chip
scan — which is why the production network keeps `lax.scan` and this is a
mesh-axis capability, not a default.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from r2d2_tpu.models.network import lstm_cell_step


def make_sp_lstm(mesh: Mesh, microbatches: int):
    """Build the pipelined time-sharded LSTM unroll over ``mesh`` axis 'sp'.

    Returns ``run(w_rec, bias, x_proj, carry0) -> (outputs, final_carry)``:
      * ``w_rec`` (H, 4H), ``bias`` (4H,) — replicated cell weights
      * ``x_proj`` (B, T, 4H) — precomputed input projection, sharded over T
      * ``carry0`` (2, B, H) — packed initial (c, h), replicated
      * outputs (B, T, H) sharded over T; final_carry (2, B, H) replicated

    Requires T % S == 0 and B % microbatches == 0.
    """
    S = mesh.shape["sp"]
    M = microbatches

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P(None, "sp", None), P()),
        out_specs=(P(None, "sp", None), P()),
        check_vma=False)
    def run(w_rec, bias, x_proj, carry0):
        k = jax.lax.axis_index("sp")
        B, Tc, G = x_proj.shape            # local chunk: T/S steps
        H = w_rec.shape[0]
        Bm = B // M
        xp = x_proj.reshape(M, Bm, Tc, G)
        c0 = carry0[0].reshape(M, Bm, H)
        h0 = carry0[1].reshape(M, Bm, H)

        def chunk_scan(carry, xp_m):
            def step(c_h, x_t):
                new = lstm_cell_step(x_t, c_h[0], c_h[1], w_rec, bias)
                return new, new[1]
            (c, h), ys = jax.lax.scan(step, carry, xp_m.swapaxes(0, 1))
            return (c, h), ys.swapaxes(0, 1)   # (Bm, Tc, H)

        right = [(i, (i + 1) % S) for i in range(S)]

        def round_body(r, state):
            outs, finals, c_prev, h_prev = state
            # the carry each stage consumes this round: stage 0 reads the
            # initial carry of microbatch r; stage k>0 receives stage k-1's
            # carry-out from the previous round over ICI
            c_in = jax.lax.ppermute(c_prev, "sp", right)
            h_in = jax.lax.ppermute(h_prev, "sp", right)
            m = r - k                      # this stage's active microbatch
            mb = jnp.clip(m, 0, M - 1)
            c_in = jnp.where(k == 0, c0[mb], c_in)
            h_in = jnp.where(k == 0, h0[mb], h_in)

            xp_m = jax.lax.dynamic_index_in_dim(xp, mb, 0, keepdims=False)
            (c_out, h_out), ys = chunk_scan((c_in, h_in), xp_m)

            active = jnp.logical_and(m >= 0, m < M)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(active, ys,
                                jax.lax.dynamic_index_in_dim(
                                    outs, mb, 0, keepdims=False)),
                mb, 0)
            # the LAST stage's carry-out is the window's final state
            write_final = jnp.logical_and(active, k == S - 1)
            fin = jnp.where(
                write_final,
                jnp.stack([c_out, h_out]),
                jax.lax.dynamic_index_in_dim(finals, mb, 0, keepdims=False))
            finals = jax.lax.dynamic_update_index_in_dim(finals, fin, mb, 0)
            return outs, finals, c_out, h_out

        outs = jnp.zeros((M, Bm, Tc, H), x_proj.dtype)
        finals = jnp.zeros((M, 2, Bm, H), x_proj.dtype)
        zeros = jnp.zeros((Bm, H), x_proj.dtype)
        outs, finals, _, _ = jax.lax.fori_loop(
            0, M + S - 1, round_body, (outs, finals, zeros, zeros))

        # finals live only on the last stage; psum replicates (others zero)
        finals = jax.lax.psum(
            jnp.where(k == S - 1, finals, jnp.zeros_like(finals)), "sp")
        final_carry = jnp.concatenate(
            [finals[:, 0].reshape(1, B, H), finals[:, 1].reshape(1, B, H)])
        return outs.reshape(B, Tc, H), final_carry

    def wrapped(w_rec: jnp.ndarray, bias: jnp.ndarray, x_proj: jnp.ndarray,
                carry0: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        B, T, _ = x_proj.shape
        if T % S:
            raise ValueError(f"T={T} not divisible by sp={S}")
        if B % M:
            raise ValueError(f"B={B} not divisible by microbatches={M}")
        # everything runs in x_proj's compute dtype (matching HoistedLSTM's
        # astype of the cell weights under a bf16 policy): f32 stored
        # carry/params would otherwise promote the gates and surface as an
        # opaque dtype mismatch inside the fori_loop body
        carry0 = carry0.astype(x_proj.dtype)
        w_rec = w_rec.astype(x_proj.dtype)
        bias = bias.astype(x_proj.dtype)
        return run(w_rec, bias, x_proj, carry0)

    return jax.jit(wrapped)
