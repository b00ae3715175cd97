"""Fused Pallas LSTM time-scan — the whole recurrent chain in ONE kernel.

Why: the profiled wall of the learner step is the 55-step serial LSTM
chain (PERF.md "Known remaining headroom"). Under `lax.scan` each step is
a separate XLA while-loop iteration: the (B, H) x (H, 4H) recurrent
matmul plus its gate math pay a loop-boundary's worth of overhead —
fusion breaks, carry round-trips, and the slice-start DMAs staging the
hoisted input projection (the ~2.1 ms span family in the captured
round-3 profile) — every iteration, ~165 times per train step (online
fwd + bwd + target fwd). Measured: ~72 us per iteration against ~10 us
of arithmetic.

This kernel runs the scan as a single Pallas grid over T:

* `Wh` is DMA'd into VMEM once (constant index map → revisiting
  optimization) and stays resident for all T steps.
* `h`/`c` live in f32 VMEM scratch across grid iterations — the carry
  never round-trips HBM.
* The per-step input projection block streams in, and the outputs
  (h sequence + saved activations for the backward pass) stream out,
  through Pallas's pipelined DMA — overlapping with the matmul instead
  of serializing as while-loop boundary copies.
* ``block_t`` processes that many consecutive timesteps per grid
  iteration (T must divide evenly; T=55 → 1, 5, 11): the in-kernel loop
  amortizes per-iteration grid/DMA bookkeeping at the cost of bigger
  VMEM blocks. The right value is a chip measurement — bench.py sweeps
  it in the plstm cells. VMEM at the reference shape (B=128, H=512):
  the backward kernel is the tight side — six (bt, 128, 512..2048)
  streamed blocks plus the revisited f32 (512, 2048) dWh block and Wh^T,
  all double-buffered. Only bf16 at bt=1 fits Mosaic's 16 MiB default
  scoped-VMEM limit on v5e (libtpu 0.0.34 refused f32 at bt=1 and both
  dtypes at bt=5 with RESOURCE_EXHAUSTED), so each call asks for what
  its blocks need (``_vmem_params``).

Pre-flight lowering audit (round 5, against the four Mosaic rejection
classes catalogued in PERF.md): every BlockSpec minor dim is
tile-aligned (128/512/2048); gate writes are static contiguous
lane-slice stores at x128 offsets (no lane concat, no strided store);
the only transpose (h_prev.T, backward) runs on f32 — the supported
32-bit sublane/lane path; no sub-32-bit casts outside supported
element-wise converts. First real-Mosaic validation happens in
``cli/chip_checks`` before any bench spend.

The backward pass is a second kernel running the grid in REVERSE
(index maps `i -> nblocks-1-i`), carrying `dh`/`dc` in scratch and
accumulating `dWh` in a revisited f32 output block; both wrapped in
`jax.custom_vjp`. Saved residuals are the post-activation gates and the
c sequence (streamed out by the forward kernel) — no recomputation
matmul in the backward step, matching XLA autodiff's op count. The
non-differentiated path (target-network unrolls) takes a lean forward
variant with no residual traffic.

Numerics: the matmul feeds the MXU in the compute dtype with f32
accumulation; gate math and carries are f32 throughout, rounding once
into the storage dtype per step — at least as accurate as the
`lax.scan` path, which carries bf16 under the bf16 policy (tolerance-
and loss-parity-tested like the bf16 policy itself).

Replaces the serial-chain half of the reference's cuDNN `nn.LSTM`
(/root/reference/model.py:33); the input projection half is already
hoisted into one big MXU matmul by `models/network.py HoistedLSTM`.
Gated by `network.pallas_lstm` (tri-state, default "off" until the TPU
A/B lands — bench cells `bf16_spd16_plstm*`).
"""

import functools

import jax
import jax.numpy as jnp


def lstm_scan_reference(xpb: jnp.ndarray, wh: jnp.ndarray,
                        c0: jnp.ndarray, h0: jnp.ndarray):
    """jnp twin (lax.scan) — the test oracle and non-TPU fallback.

    ``xpb``: (T, B, 4H) input projection WITH bias already folded in;
    ``wh``: (H, 4H); ``c0``/``h0``: (B, H). Gate order i, f, g, o —
    identical to models/network.py lstm_cell_step.
    Returns (h_seq (T, B, H), (c_fin, h_fin)).
    """

    def step(carry, xp):
        c, h = carry
        gates = xp + h @ wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (c, h), h

    (c, h), hs = jax.lax.scan(step, (c0, h0), xpb)
    return hs, (c, h)


def _cell_math(hidden: int, xp_f32, wh_ref, h_s, c_s):
    """One LSTM step on the f32 VMEM carries; returns the gate activations
    and new carries (all f32 registers) and updates the scratches. Shared
    by the residual-saving and lean forward kernels so they cannot
    diverge."""
    cd = wh_ref.dtype
    gates = xp_f32 + jax.lax.dot_general(
        h_s[:].astype(cd), wh_ref[:],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    i_g = jax.nn.sigmoid(gates[:, :hidden])
    f_g = jax.nn.sigmoid(gates[:, hidden:2 * hidden])
    g_g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o_g = jax.nn.sigmoid(gates[:, 3 * hidden:])
    c_new = f_g * c_s[:] + i_g * g_g
    h_new = o_g * jnp.tanh(c_new)
    c_s[:] = c_new
    h_s[:] = h_new
    return i_g, f_g, g_g, o_g, c_new, h_new


def _fwd_kernel(hidden: int, block_t: int, xpb_ref, wh_ref, c0_ref, h0_ref,
                hseq_ref, cseq_ref, acts_ref, h_s, c_s):
    from jax.experimental import pallas as pl

    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _():
        h_s[:] = h0_ref[:].astype(jnp.float32)
        c_s[:] = c0_ref[:].astype(jnp.float32)

    out_dtype = hseq_ref.dtype
    for j in range(block_t):
        i_g, f_g, g_g, o_g, c_new, h_new = _cell_math(
            hidden, xpb_ref[j].astype(jnp.float32), wh_ref, h_s, c_s)
        hseq_ref[j] = h_new.astype(out_dtype)
        cseq_ref[j] = c_new.astype(out_dtype)
        # four static lane-slice stores, not a lane concat — slice writes
        # at tile-multiple offsets are the Mosaic-safe lowering
        acts_ref[j, :, :hidden] = i_g.astype(out_dtype)
        acts_ref[j, :, hidden:2 * hidden] = f_g.astype(out_dtype)
        acts_ref[j, :, 2 * hidden:3 * hidden] = g_g.astype(out_dtype)
        acts_ref[j, :, 3 * hidden:] = o_g.astype(out_dtype)


def _fwd_kernel_lean(hidden: int, nblocks: int, block_t: int, xpb_ref,
                     wh_ref, c0_ref, h0_ref, hseq_ref, cfin_ref, h_s, c_s):
    # forward-only variant: no backward residuals — the target-network
    # unrolls (and any other non-differentiated call) must not pay the
    # (T, B, 5H) HBM write traffic of cseq + acts they will never read
    from jax.experimental import pallas as pl

    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _():
        h_s[:] = h0_ref[:].astype(jnp.float32)
        c_s[:] = c0_ref[:].astype(jnp.float32)

    c_new = None
    for j in range(block_t):
        _, _, _, _, c_new, h_new = _cell_math(
            hidden, xpb_ref[j].astype(jnp.float32), wh_ref, h_s, c_s)
        hseq_ref[j] = h_new.astype(hseq_ref.dtype)

    @pl.when(blk == nblocks - 1)
    def _():
        cfin_ref[:] = c_new.astype(cfin_ref.dtype)


# v5e's TensorCore has 128 MiB of VMEM; Mosaic scopes a kernel to 16 MiB
# unless told otherwise. Leave the rest of the program a quarter of it.
_VMEM_CEILING = 96 << 20


def _vmem_params(specs, arrays, scratch_bytes: int):
    """CompilerParams sized from the call's own blocks: Pallas double-
    buffers every in/out block (``specs`` with the dtypes of the matching
    ``arrays``), the scratches are single, and the unrolled in-kernel step
    math needs headroom for its f32 temporaries."""
    import math

    from jax.experimental.pallas import tpu as pltpu

    need = scratch_bytes + 2 * sum(
        math.prod(spec.block_shape) * jnp.dtype(arr.dtype).itemsize
        for spec, arr in zip(specs, arrays))
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(need + (16 << 20), _VMEM_CEILING))


def _fwd_call(xpb, wh, c0, h0, interpret, block_t, save_residuals=True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nsteps, batch, gdim = xpb.shape
    hidden = gdim // 4
    dtype = xpb.dtype
    nblocks = nsteps // block_t
    bt = block_t
    if save_residuals:
        kernel = functools.partial(_fwd_kernel, hidden, bt)
        out_specs = [
            pl.BlockSpec((bt, batch, hidden), lambda t: (t, 0, 0)),
            pl.BlockSpec((bt, batch, hidden), lambda t: (t, 0, 0)),
            pl.BlockSpec((bt, batch, gdim), lambda t: (t, 0, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((nsteps, batch, hidden), dtype),
            jax.ShapeDtypeStruct((nsteps, batch, hidden), dtype),
            jax.ShapeDtypeStruct((nsteps, batch, gdim), dtype),
        ]
    else:
        kernel = functools.partial(_fwd_kernel_lean, hidden, nblocks, bt)
        out_specs = [
            pl.BlockSpec((bt, batch, hidden), lambda t: (t, 0, 0)),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((nsteps, batch, hidden), dtype),
            jax.ShapeDtypeStruct((batch, hidden), dtype),
        ]
    in_specs = [
        pl.BlockSpec((bt, batch, gdim), lambda t: (t, 0, 0)),
        pl.BlockSpec((hidden, gdim), lambda t: (0, 0)),
        pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
        pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
    ]
    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((batch, hidden), jnp.float32),
            pltpu.VMEM((batch, hidden), jnp.float32),
        ],
        compiler_params=_vmem_params(
            in_specs + out_specs, [xpb, wh, c0, h0] + out_shape,
            scratch_bytes=2 * batch * hidden * 4),
        interpret=interpret,
    )(xpb, wh, c0, h0)


def _bwd_kernel(hidden: int, nblocks: int, block_t: int,
                dhseq_ref, acts_ref, cseq_ref, cprevb_ref, hprevb_ref,
                wht_ref, c0_ref, h0_ref, dcfin_ref, dhfin_ref,
                dxpb_ref, dwh_ref, dc0_ref, dh0_ref, dh_s, dc_s):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    blk = nblocks - 1 - i                    # blocks processed descending

    @pl.when(i == 0)
    def _():
        dh_s[:] = dhfin_ref[:].astype(jnp.float32)
        dc_s[:] = dcfin_ref[:].astype(jnp.float32)
        dwh_ref[:] = jnp.zeros_like(dwh_ref)

    out_dtype = dxpb_ref.dtype
    cd = wht_ref.dtype
    for j in reversed(range(block_t)):
        acts = acts_ref[j].astype(jnp.float32)
        i_g = acts[:, :hidden]
        f_g = acts[:, hidden:2 * hidden]
        g_g = acts[:, 2 * hidden:3 * hidden]
        o_g = acts[:, 3 * hidden:]
        if j > 0:
            # in-block predecessor: c from the saved sequence; h
            # recomputed as o*tanh(c) (cheaper than streaming hseq twice)
            c_prev = cseq_ref[j - 1].astype(jnp.float32)
            h_prev = (acts_ref[j - 1, :, 3 * hidden:].astype(jnp.float32)
                      * jnp.tanh(c_prev))
        else:
            # block boundary: previous block's LAST element; at t == 0 the
            # prev-block stream is a clamped re-read — select the initial
            # carries instead (both operands resident in VMEM)
            first = blk == 0
            c_prev = jnp.where(first, c0_ref[:].astype(jnp.float32),
                               cprevb_ref[block_t - 1].astype(jnp.float32))
            h_prev = jnp.where(first, h0_ref[:].astype(jnp.float32),
                               hprevb_ref[block_t - 1].astype(jnp.float32))

        dh_total = dhseq_ref[j].astype(jnp.float32) + dh_s[:]
        tanh_c = jnp.tanh(cseq_ref[j].astype(jnp.float32))
        do = dh_total * tanh_c
        dc = dc_s[:] + dh_total * o_g * (1.0 - tanh_c * tanh_c)
        di = dc * g_g
        dg = dc * i_g
        df = dc * c_prev
        # pre-activation gate grads (sigmoid' = s(1-s); tanh' = 1-t^2),
        # written as four static lane-slice stores into the dxpb output
        # block (no lane concat — see the forward kernel), then read back
        # whole for the two dots. The readback rounds through the storage
        # dtype — the same rounding the dots' MXU-dtype cast applies
        # anyway.
        dxpb_ref[j, :, :hidden] = (di * i_g * (1.0 - i_g)).astype(out_dtype)
        dxpb_ref[j, :, hidden:2 * hidden] = (
            df * f_g * (1.0 - f_g)).astype(out_dtype)
        dxpb_ref[j, :, 2 * hidden:3 * hidden] = (
            dg * (1.0 - g_g * g_g)).astype(out_dtype)
        dxpb_ref[j, :, 3 * hidden:] = (
            do * o_g * (1.0 - o_g)).astype(out_dtype)

        dg_cd = dxpb_ref[j].astype(cd)
        dh_s[:] = jax.lax.dot_general(
            dg_cd, wht_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # transpose in f32 (32-bit sublane/lane transpose is the supported
        # Mosaic path on v5e), cast to the MXU dtype after
        dwh_ref[:] += jax.lax.dot_general(
            h_prev.T.astype(cd), dg_cd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_s[:] = dc * f_g

    @pl.when(i == nblocks - 1)
    def _():
        # after the t == 0 update, the scratches hold d h_{-1} / d c_{-1}
        dh0_ref[:] = dh_s[:]
        dc0_ref[:] = dc_s[:]


def _bwd_call(wh, c0, h0, hseq, cseq, acts, dhseq, dcfin, dhfin, interpret,
              block_t):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nsteps, batch, gdim = acts.shape
    hidden = gdim // 4
    wht = wh.T                                            # (4H, H)
    nblocks = nsteps // block_t
    bt = block_t

    def rev(t_idx):
        return lambda i: (t_idx(i), 0, 0)

    last = nblocks - 1
    prev = lambda i: jnp.maximum(last - 1 - i, 0)
    operands = (dhseq, acts, cseq, cseq, hseq, wht, c0, h0, dcfin, dhfin)
    in_specs = [
        pl.BlockSpec((bt, batch, hidden), rev(lambda i: last - i)),  # dhseq
        pl.BlockSpec((bt, batch, gdim), rev(lambda i: last - i)),    # acts
        pl.BlockSpec((bt, batch, hidden), rev(lambda i: last - i)),  # c_t
        pl.BlockSpec((bt, batch, hidden), rev(prev)),            # c prevblk
        pl.BlockSpec((bt, batch, hidden), rev(prev)),            # h prevblk
        pl.BlockSpec((gdim, hidden), lambda i: (0, 0)),              # Wh^T
        pl.BlockSpec((batch, hidden), lambda i: (0, 0)),             # c0
        pl.BlockSpec((batch, hidden), lambda i: (0, 0)),             # h0
        pl.BlockSpec((batch, hidden), lambda i: (0, 0)),             # dc_fin
        pl.BlockSpec((batch, hidden), lambda i: (0, 0)),             # dh_fin
    ]
    out_specs = [
        pl.BlockSpec((bt, batch, gdim), rev(lambda i: last - i)),    # dxpb
        pl.BlockSpec((hidden, gdim), lambda i: (0, 0)),              # dWh
        pl.BlockSpec((batch, hidden), lambda i: (0, 0)),             # dc0
        pl.BlockSpec((batch, hidden), lambda i: (0, 0)),             # dh0
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nsteps, batch, gdim), dhseq.dtype),
        jax.ShapeDtypeStruct((hidden, gdim), jnp.float32),
        jax.ShapeDtypeStruct((batch, hidden), jnp.float32),
        jax.ShapeDtypeStruct((batch, hidden), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hidden, nblocks, bt),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((batch, hidden), jnp.float32),
            pltpu.VMEM((batch, hidden), jnp.float32),
        ],
        compiler_params=_vmem_params(
            in_specs + out_specs, list(operands) + out_shape,
            scratch_bytes=2 * batch * hidden * 4),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lstm_scan(interpret, block_t, xpb, wh, c0, h0):
    # the NON-differentiated path (target-network unrolls): lean kernel,
    # no residual traffic. Under jax.grad, _lstm_scan_fwd runs instead.
    hseq, cfin = _fwd_call(xpb, wh, c0, h0, interpret, block_t,
                           save_residuals=False)
    return hseq, (cfin, hseq[-1])


def _lstm_scan_fwd(interpret, block_t, xpb, wh, c0, h0):
    hseq, cseq, acts = _fwd_call(xpb, wh, c0, h0, interpret, block_t)
    out = (hseq, (cseq[-1], hseq[-1]))
    return out, (wh, c0, h0, hseq, cseq, acts)


def _lstm_scan_bwd(interpret, block_t, res, cts):
    wh, c0, h0, hseq, cseq, acts = res
    dhseq, (dcfin, dhfin) = cts
    dxpb, dwh, dc0, dh0 = _bwd_call(
        wh, c0, h0, hseq, cseq, acts, dhseq, dcfin, dhfin, interpret,
        block_t)
    return (dxpb, dwh.astype(wh.dtype), dc0.astype(c0.dtype),
            dh0.astype(h0.dtype))


_lstm_scan.defvjp(_lstm_scan_fwd, _lstm_scan_bwd)


def lstm_scan_pallas(xpb: jnp.ndarray, wh: jnp.ndarray, c0: jnp.ndarray,
                     h0: jnp.ndarray, interpret: bool = False,
                     block_t: int = 1):
    """Fused-kernel LSTM scan (differentiable). Same signature/returns as
    ``lstm_scan_reference``; ``interpret=True`` runs both kernels on any
    backend (the CPU test mesh). ``block_t``: timesteps per grid
    iteration (must divide T; NetworkConfig.pallas_lstm_block)."""
    if xpb.shape[0] % block_t:
        raise ValueError(
            f"block_t={block_t} does not divide the {xpb.shape[0]}-step "
            "sequence — pick a divisor (network.pallas_lstm_block)")
    return _lstm_scan(interpret, block_t, xpb, wh, c0, h0)
