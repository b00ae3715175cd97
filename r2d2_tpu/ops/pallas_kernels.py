"""Pallas TPU kernels for the learner's data-decode hot path.

``stack_frames``: expand a raw uint8 frame row into the frame-stacked,
normalized f32 observation tensor the conv torso consumes:

    obs (B, T+K-1, H, W) uint8  →  (B, T, H, W, K) float32 in [0, 1]
    out[b, t, h, w, k] = obs[b, t + k, h, w] / 255

This is the reference learner's obs_idx gather + /255
(/root/reference/worker.py:310,330-331) — a pure data-movement + elementwise
op. The XLA lowering of the jnp version materializes the (B, T, K, H, W)
uint8 gather, then a transposed f32 copy (5x the input bytes through HBM);
the pallas kernel streams each batch row through VMEM once and emits the
stacked f32 directly, fusing window expansion, transpose, dtype conversion,
and normalization.

Grid: (batch, seq_window), t fastest. The input spec maps every t to the
same uint8 row block, so Pallas's revisiting optimization DMAs each row
into VMEM once per batch index and the K-frame windows are VMEM slices;
the output streams one timestep slab per program.

Layout note (measured, round 3): the kernel emits (B, T, K, H, W) — K
*before* the spatial dims — and the wrapper transposes to the public
(B, T, H, W, K) contract outside the kernel. Emitting K minor-most
directly is catastrophic on TPU: the (8, 128) register tile pads the
trailing (84, 4) dims to (88, 128), inflating the HBM buffer 32x (26 GB
at batch 128) and a full-window VMEM block to 416 MB. With (84, 84)
minor the padding is 1.6x and the per-timestep VMEM slab is ~180 KB; the
explicit transpose lands inside the jitted train step where XLA folds it
into its own layout assignment for the conv torso. No custom VJP is
needed: observations carry no gradient (grads flow to params only).

``stack_frames_reference`` is the jnp twin — the test oracle and the
non-TPU fallback.
"""

import functools

import jax
import jax.numpy as jnp

from r2d2_tpu.ops.indexing import frame_stack_indices


def stack_frames_reference(obs: jnp.ndarray, seq_window: int,
                           frame_stack: int,
                           out_dtype=jnp.float32,
                           out_height=None,
                           out_width=None) -> jnp.ndarray:
    """jnp twin: gather + transpose + normalize (XLA-lowered).
    ``out_dtype``: emit in the network's compute dtype — normalization
    always happens in f32 and rounds once at the end, so a bf16 output is
    bit-identical to XLA's own f32→bf16 cast at the conv boundary (which
    the MXU's default precision inserts anyway); emitting it here skips
    materializing the 4x-larger f32 intermediate.
    ``out_height``/``out_width``: strip tile padding from exact-gather
    storage rows (ReplaySpec.stored_frame_height/_width) — the network
    always sees the true frame shape."""
    fsi = frame_stack_indices(seq_window, frame_stack)       # (T, K)
    stacked = obs[:, fsi]                                     # (B, T, K, H, W)
    if out_height is not None and out_height != obs.shape[2]:
        stacked = stacked[:, :, :, :out_height, :]
    if out_width is not None and out_width != obs.shape[3]:
        stacked = stacked[:, :, :, :, :out_width]
    out = stacked.transpose(0, 1, 3, 4, 2).astype(jnp.float32) / 255.0
    return out.astype(out_dtype)


def _stack_kernel(frame_stack: int, out_dtype, out_height: int,
                  out_width: int, in_ref, out_ref):
    # in_ref: (1, T+K-1, H_stored, W_stored) uint8 (whole row, revisited
    # across t); out_ref: (1, 1, K, out_height, out_width) out_dtype —
    # this program's timestep slab. out_height/out_width < stored strip
    # exact-gather tile padding (static sublane/lane-dim slices).
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    inv = jnp.float32(1.0 / 255.0)
    for k in range(frame_stack):
        frame = in_ref[0, pl.dslice(t + k, 1)]               # (1, H, W) u8
        # Mosaic can't lower uint8 -> float32 directly (round-2 bench failure);
        # widen through int32 first, which it can, then convert. The
        # normalization rounds once from f32 into out_dtype — identical to
        # XLA's own cast at the conv boundary under a bf16 policy.
        widened = frame[0, :out_height, :out_width].astype(
            jnp.int32).astype(jnp.float32)
        out_ref[0, 0, k] = (widened * inv).astype(out_dtype)


def _decode_plane(in_ref, t, k, out_height: int, out_width: int):
    """One frame plane, decoded to normalized f32 (H, W) in registers.
    Mosaic can't cast uint8 -> f32 directly (round 2): widen via i32."""
    from jax.experimental import pallas as pl

    frame = in_ref[0, pl.dslice(t + k, 1)]                   # (1, H, W) u8
    widened = frame[0, :out_height, :out_width].astype(
        jnp.int32).astype(jnp.float32)
    return widened * jnp.float32(1.0 / 255.0)


def _stack_kernel_nhwc32(frame_stack: int, out_dtype, out_height: int,
                         out_width: int, in_ref, out_ref):
    # NHWC-emitting variant for 32-bit out_dtype: interleave K into the
    # LANE dim (out lane index = w*K + k) with one strided store per
    # plane, so the public (B, T, H, W, K) contract is a free reshape of
    # the kernel output — no post-kernel transpose. The relayout happens
    # in VMEM registers per timestep instead of as an HBM round-trip (the
    # 1.6 ms/step layout copy in the round-3 profile). Strided stores are
    # implemented for 32-bit data only (v5e Mosaic), hence the packed
    # 16-bit variant below.
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    for k in range(frame_stack):
        val = _decode_plane(in_ref, t, k, out_height, out_width)
        out_ref[0, 0, :, pl.Slice(k, out_width, frame_stack)] = (
            val.astype(out_dtype))


def _stack_kernel_nhwc16(frame_stack: int, out_dtype, out_height: int,
                         out_width: int, in_ref, out_ref):
    # NHWC-emitting variant for 16-bit out_dtype (the bf16 policy).
    # Mosaic rejects every direct 16-bit relayout route on v5e: bf16
    # minor-dim insertion ("32-bit only"), the (H,W,K)->(H,W*K)
    # lane-merge reshape, and 16-bit strided stores. The working route
    # is PAIR PACKING: bitcast each bf16 plane to u16, pack planes
    # 2p/2p+1 into the low/high halves of one i32 vector, and emit with
    # 32-bit strided stores into an i32 output at lane j = w*(K/2) + p.
    # The wrapper's i32 -> out_dtype bitcast appends a trailing dim of 2
    # indexing [low, high] bits (XLA narrowing convention), so final
    # bf16 lane l = j*2 + e = w*K + 2p + e = w*K + k — exactly NHWC.
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    pairs = frame_stack // 2
    for p in range(pairs):
        lo = jax.lax.bitcast_convert_type(
            _decode_plane(in_ref, t, 2 * p, out_height, out_width)
            .astype(out_dtype), jnp.uint16).astype(jnp.int32)
        hi = jax.lax.bitcast_convert_type(
            _decode_plane(in_ref, t, 2 * p + 1, out_height, out_width)
            .astype(out_dtype), jnp.uint16).astype(jnp.int32)
        packed = jax.lax.bitwise_or(lo, jax.lax.shift_left(hi, 16))
        out_ref[0, 0, :, pl.Slice(p, out_width, pairs)] = packed


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def stack_frames_pallas(obs: jnp.ndarray, seq_window: int, frame_stack: int,
                        interpret: bool = False,
                        out_dtype=jnp.float32,
                        out_height=None,
                        nhwc: bool = False,
                        out_width=None) -> jnp.ndarray:
    """Pallas implementation; ``interpret=True`` runs it on any backend
    (tests use it on the CPU mesh). ``out_height``/``out_width``: emit only
    the first out_height x out_width pixels of each (possibly tile-padded)
    stored frame. ``nhwc``: emit the NHWC layout in-kernel (no post-kernel
    transpose — see _stack_kernel_nhwc); optim.pallas_decode_layout
    selects it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, row_len, height, width = obs.shape
    assert row_len >= seq_window + frame_stack - 1
    out_height = height if out_height is None else out_height
    out_width = width if out_width is None else out_width

    if nhwc:
        itemsize = jnp.dtype(out_dtype).itemsize
        if itemsize == 2 and frame_stack % 2 == 0:
            # packed route (see _stack_kernel_nhwc16): i32 storage holding
            # bf16 pairs; bitcast back outside the kernel (layout-free)
            kernel = functools.partial(_stack_kernel_nhwc16, frame_stack,
                                       out_dtype, out_height, out_width)
            out_block = (1, 1, out_height, out_width * frame_stack // 2)
            store_dtype = jnp.int32
        elif itemsize == 4:
            kernel = functools.partial(_stack_kernel_nhwc32, frame_stack,
                                       out_dtype, out_height, out_width)
            out_block = (1, 1, out_height, out_width * frame_stack)
            store_dtype = out_dtype
        else:
            raise NotImplementedError(
                f"nhwc decode needs a 32-bit out_dtype or a 16-bit one "
                f"with even frame_stack; got {jnp.dtype(out_dtype).name} "
                f"with frame_stack={frame_stack}")
        out_map = lambda b, t: (b, t, 0, 0)
    else:
        kernel = functools.partial(_stack_kernel, frame_stack, out_dtype,
                                   out_height, out_width)
        out_block = (1, 1, frame_stack, out_height, out_width)
        out_map = lambda b, t: (b, t, 0, 0, 0)
        store_dtype = out_dtype
    out = pl.pallas_call(
        kernel,
        grid=(batch, seq_window),
        in_specs=[pl.BlockSpec(
            (1, row_len, height, width),
            lambda b, t: (b, 0, 0, 0),   # constant in t: one DMA per row
            memory_space=pltpu.VMEM,
        )],
        out_specs=pl.BlockSpec(out_block, out_map,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (batch, seq_window) + out_block[2:], store_dtype),
        interpret=interpret,
    )(obs)
    if nhwc:
        if store_dtype != out_dtype:
            # i32 -> (..., 2) out_dtype; index 0 = low 16 bits (XLA
            # narrowing convention), matching the kernel's pack order
            out = jax.lax.bitcast_convert_type(out, out_dtype)
        # lane index = w*K + k, so this reshape is layout-free
        return out.reshape(batch, seq_window, out_height, out_width,
                           frame_stack)
    return out.transpose(0, 1, 3, 4, 2)                      # (B, T, H, W, K)


def stack_frames_pallas_nhwc(obs: jnp.ndarray, seq_window: int,
                             frame_stack: int, interpret: bool = False,
                             out_dtype=jnp.float32,
                             out_height=None,
                             out_width=None) -> jnp.ndarray:
    """NHWC-emitting decode (stack_frames_pallas with nhwc=True)."""
    return stack_frames_pallas(obs, seq_window, frame_stack, interpret,
                               out_dtype, out_height, nhwc=True,
                               out_width=out_width)


def resolve_pallas_setting(setting, field: str = "pallas setting") -> bool:
    """Resolve a pallas tri-state config knob: "on", "off", or "auto" =
    pallas iff the default backend is TPU (the measured winner there —
    builders, round 3 — while Mosaic cannot compile for CPU/GPU backends). Accepts
    legacy bools (configs serialized before the tri-state existed) and
    their CLI string spellings (--optim.pallas_obs_decode=true coerces to
    the literal string "true")."""
    if isinstance(setting, bool):
        return setting
    lowered = str(setting).lower()
    if lowered == "auto":
        return jax.default_backend() == "tpu"
    if lowered in ("on", "true", "1", "yes"):
        return True
    if lowered in ("off", "false", "0", "no"):
        return False
    raise ValueError(
        f"{field} must be 'on', 'off', or 'auto'; got {setting!r}")


def resolve_pallas_obs_decode(setting) -> bool:
    return resolve_pallas_setting(setting, "pallas_obs_decode")


def stack_frames(obs: jnp.ndarray, seq_window: int, frame_stack: int,
                 use_pallas: bool = False,
                 out_dtype=jnp.float32,
                 out_height=None,
                 nhwc: bool = False,
                 out_width=None) -> jnp.ndarray:
    """Dispatch: pallas on TPU when requested (``nhwc`` selects the
    transpose-free NHWC-emitting kernel), jnp otherwise."""
    if use_pallas:
        return stack_frames_pallas(obs, seq_window, frame_stack,
                                   out_dtype=out_dtype, out_height=out_height,
                                   nhwc=nhwc, out_width=out_width)
    return stack_frames_reference(obs, seq_window, frame_stack,
                                  out_dtype=out_dtype, out_height=out_height,
                                  out_width=out_width)


# ---------------------------------------------------------------------------
# Replay-sample window gather (the learner-side obs slice of
# /root/reference/worker.py:140-166, which the reference runs as a
# 128-iteration Python loop in the buffer process).


def gather_rows_reference(ring: jnp.ndarray, block_idx: jnp.ndarray,
                          start: jnp.ndarray, window: int) -> jnp.ndarray:
    """vmapped dynamic-slice twin — correct everywhere, but XLA lowers the
    batched start indices to a generic uint8 gather that measures ~5.5 ms
    at the production shape on TPU v5e (round-3 analysis)."""
    def one(b, t0):
        return jax.lax.dynamic_slice(
            ring[b], (t0, 0, 0), (window,) + ring.shape[2:])
    return jax.vmap(one)(block_idx, start)


@functools.partial(jax.jit, static_argnums=(3, 4))
def gather_rows_pallas(ring: jnp.ndarray, block_idx: jnp.ndarray,
                       start: jnp.ndarray, window: int,
                       interpret: bool = False) -> jnp.ndarray:
    """Scalar-prefetch row gather: out[i] = ring[block_idx[i],
    start[i] : start[i]+window].

    One program per sampled sequence. The prefetched block index drives the
    input BlockSpec, so each program's whole ring row is DMA'd into VMEM
    and the dynamic window offset becomes a VMEM slice. Reads amplify by
    row_len/window (~7x at the production shape) but stay sequential DMAs —
    measured 2.15 ms vs the 5.5 ms XLA gather (2.6x). The exact-read
    variants lose: per-frame blocks pay too many small DMAs (2.8 ms), and
    a raw HBM->HBM async copy is rejected by Mosaic (window slices must be
    tile-aligned; H=84 is not)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_rows, row_len, height, width = ring.shape
    batch = block_idx.shape[0]

    def kernel(bi_ref, st_ref, in_ref, out_ref):
        i = pl.program_id(0)
        out_ref[0] = in_ref[0, pl.dslice(st_ref[i], window)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[pl.BlockSpec(
            (1, row_len, height, width),
            lambda i, bi, st: (bi[i], 0, 0, 0),
        )],
        out_specs=pl.BlockSpec(
            (1, window, height, width),
            lambda i, bi, st: (i, 0, 0, 0),
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (batch, window, height, width), ring.dtype),
        interpret=interpret,
    )(block_idx, start, ring)


@functools.partial(jax.jit, static_argnums=(3, 4))
def gather_rows_exact_pallas(ring: jnp.ndarray, block_idx: jnp.ndarray,
                             start: jnp.ndarray, window: int,
                             interpret: bool = False) -> jnp.ndarray:
    """EXACT-read row gather: one HBM→HBM async copy of just the window
    slice per sampled sequence — no row amplification (gather_rows_pallas
    reads the whole ring row, ~7x the window bytes at the production
    shape).

    Mosaic requires BOTH minor dims of the copied slice to be
    tile-aligned: H=84 was rejected round 3, and an H-only pad was
    rejected round 4 (dim-3 tiling is 128), which is why this variant
    pairs with ``replay.pallas_exact_gather`` (storage padded 84x84 →
    96x128, the uint8 (32, 128) tile). Whether the padded copy
    compiles/wins is a TPU measurement (bench.py's pad-gather cell);
    interpret mode pins the semantics either way."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_rows, row_len, height, width = ring.shape
    batch = block_idx.shape[0]

    def kernel(bi_ref, st_ref, hbm_ref, out_ref, sem):
        i = pl.program_id(0)
        copy = pltpu.make_async_copy(
            hbm_ref.at[bi_ref[i], pl.dslice(st_ref[i], window)],
            out_ref.at[i],
            sem)
        copy.start()
        copy.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (batch, window, height, width), ring.dtype),
        interpret=interpret,
    )(block_idx, start, ring)


def gather_rows(ring: jnp.ndarray, block_idx: jnp.ndarray, start: jnp.ndarray,
                window: int, use_pallas: bool = False,
                exact_read: bool = False) -> jnp.ndarray:
    """Dispatch: pallas on TPU when requested (exact_read selects the
    async-copy window gather), vmapped dynamic-slice otherwise."""
    if use_pallas and exact_read:
        return gather_rows_exact_pallas(ring, block_idx, start, window)
    if use_pallas:
        return gather_rows_pallas(ring, block_idx, start, window)
    return gather_rows_reference(ring, block_idx, start, window)
