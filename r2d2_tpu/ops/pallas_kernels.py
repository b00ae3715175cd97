"""Pallas TPU kernels for the learner's data-decode hot path.

``stack_frames``: expand a raw uint8 frame row into the frame-stacked,
normalized observation tensor the conv torso consumes, in the network's
compute dtype:

    obs (B, T+K-1, H, W) uint8  →  (B, T, H, W, K) in [0, 1]
    out[b, t, h, w, k] = obs[b, t + k, h, w] / 255

This is the reference learner's obs_idx gather + /255
(/root/reference/worker.py:310,330-331) — a pure data-movement + elementwise
op: normalised in f32, rounded once into the compute dtype. Which
implementation runs follows from the input's shapes (``decode_route``).

On the TPU, where the input fits it, ``stack_frames_lanes`` writes the
stacked observations in the layout the first convolution reads, and hands
them over as ``LaneFrames`` — the torso's batch, already flattened. XLA runs
the whole torso with the FRAME INDEX IN LANES: minor to major N, K, W, H,
tile (4, 128) with two bf16 rows to a 32-bit word, so the K = 4 planes fill a
tile's sublanes and 128 frames its lanes (bf16[7040,84,84,4]{0,3,2,1:T(4,128)
(2,1)} at B128 x T55, 397 MB). The kernel's output (H, W, tile, K, 128) has
those very bytes, XLA's transpose of it is a bitcast, and nothing is copied
between the kernel and the convolution (`k_decode_roofline` 92.6%: ledger,
PR 26).

How: a lane tile is 128 frames of one time step — 128 sequences' step t
where the batch fills the lanes, or step i of each of 128/B time segments
where it does not (``lane_order``; the network brings the latent back to
(B, T, D), ``LaneFrames.sequence``). Plane k of tile i is stored step i + k
of those frames, so the kernel walks the stored steps once: 128 frames' rows
arrive by DMA as 32-bit words (four stored rows a word), one sublane-strided
load puts a word row of all 128 frames on sublanes, a 128 x 128 transpose
puts the frames on lanes, each byte is normalised and rounded (to bf16 by
hand, in integer arithmetic: two planes share a word), kept in VMEM for the
K - 1 tiles that still need it, and written with 32-bit strided stores.
Mosaic refuses 16-bit strided stores, lane-merging reshapes and a trailing
K = 4, so none is on this route. The kernel's lowering is the same size for
every window and batch: time, rows and segments loop in the grid or a
``fori_loop``.

The planar kernel ``stack_frames_pallas`` is the Pallas path of the shapes
the lanes route does not take (a batch that does not tile 128 lanes, storage
that is not tile-padded, an odd stack in bf16): it emits (B, T, K, H, W) — K
*before* the spatial dims — and the wrapper transposes to the public
(B, T, H, W, K) contract outside the kernel. Emitting K minor-most
directly is catastrophic on TPU: the (8, 128) register tile pads the
trailing (84, 4) dims to (88, 128), inflating the HBM buffer 32x (26 GB
at batch 128). With (84, 84) minor the padding is 1.6x and the
per-timestep VMEM slab is ~180 KB; XLA then pays the layout copy in front
of the conv torso. Grid (batch, seq_window), t fastest: the input spec
maps every t to the same uint8 row block, so each row is DMA'd once per
batch index and the K-frame windows are VMEM slices. No custom VJP is
needed anywhere here: observations carry no gradient.

``stack_frames_reference`` is the jnp twin — the test oracle and the
non-TPU fallback.
"""

import dataclasses
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


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def stack_frames_pallas(obs: jnp.ndarray, seq_window: int, frame_stack: int,
                        interpret: bool = False,
                        out_dtype=jnp.float32,
                        out_height=None,
                        out_width=None) -> jnp.ndarray:
    """Pallas implementation; ``interpret=True`` runs it on any backend
    (tests use it on the CPU mesh). ``out_height``/``out_width``: emit only
    the first out_height x out_width pixels of each (possibly tile-padded)
    stored frame."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, row_len, height, width = obs.shape
    assert row_len >= seq_window + frame_stack - 1
    out_height = height if out_height is None else out_height
    out_width = width if out_width is None else out_width

    kernel = functools.partial(_stack_kernel, frame_stack, out_dtype,
                               out_height, out_width)
    out_block = (1, 1, frame_stack, out_height, out_width)
    out = pl.pallas_call(
        kernel,
        grid=(batch, seq_window),
        in_specs=[pl.BlockSpec(
            (1, row_len, height, width),
            lambda b, t: (b, 0, 0, 0),   # constant in t: one DMA per row
            memory_space=pltpu.VMEM,
        )],
        out_specs=pl.BlockSpec(out_block, lambda b, t: (b, t, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (batch, seq_window) + out_block[2:], out_dtype),
        interpret=interpret,
    )(obs)
    return out.transpose(0, 1, 3, 4, 2)                      # (B, T, H, W, K)


# ---------------------------------------------------------------------------
# Frame-in-lanes decode: the first convolution's own layout.

LANES = 128          # lanes of a vector register: frames of one lane tile
_SUBLANE_ROWS = 32   # stored uint8 rows of one (32, 128) tile: an input block
_WORD_ROWS = _SUBLANE_ROWS // 4   # the same block as rows of 32-bit words


def lane_order(batch: int, seq_window: int):
    """(columns, group, steps) of the lane order, or None where the batch
    does not tile the lanes. A lane tile holds 128 frames: one time step of
    a column of 128 sequences (group = 1), or, of a batch under 128, one
    step of each of ``group`` time segments ``steps`` steps long. Frame
    n = ((c * steps + i) * group + s) * (batch / columns) + b' is time step
    t = s * steps + i of sequence b = c * 128 + b'. Steps past the window
    (group * steps > seq_window) repeat the window's last frames and are
    dropped by ``LaneFrames.sequence``."""
    if batch % LANES == 0:
        return batch // LANES, 1, seq_window
    if LANES % batch == 0:
        group = LANES // batch
        return 1, group, -(-seq_window // group)
    return None


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["frames"], meta_fields=["batch", "seq_window"])
@dataclasses.dataclass(frozen=True)
class LaneFrames:
    """Stacked observations as the torso's batch, in ``lane_order``:
    ``frames`` is (columns * steps * 128, H, W, K). Stands in for the
    (B, T, H, W, K) array of the network's contract (``shape`` is that
    logical shape): the network runs its torso over ``frames`` as they are
    and brings the latent back to (B, T, D) with ``sequence``."""
    frames: jnp.ndarray
    batch: int
    seq_window: int

    @property
    def shape(self):
        return (self.batch, self.seq_window) + self.frames.shape[1:]

    def sequence(self, rows: jnp.ndarray) -> jnp.ndarray:
        """(frames, D) rows in lane order -> (B, T, D)."""
        columns, group, steps = lane_order(self.batch, self.seq_window)
        rows = rows.reshape(columns, steps, group, self.batch // columns,
                            rows.shape[-1])
        rows = rows.transpose(0, 3, 2, 1, 4).reshape(
            self.batch, group * steps, rows.shape[-1])
        return rows[:, :self.seq_window]


def lane_fill(batch: int, seq_window: int) -> float:
    """Share of the torso's batch in ``lane_order`` that is window frames
    (the rest pads the last lane tile of each segment)."""
    columns, _, steps = lane_order(batch, seq_window)
    return batch * seq_window / (columns * steps * LANES)


def lanes_route(obs_shape, seq_window: int, frame_stack: int,
                out_dtype) -> bool:
    """Whether ``stack_frames_lanes`` takes this input, from what the input
    shows: the batch tiles the lanes, the stored frame is padded to whole
    uint8 tiles no wider than the lanes (the exact gather's storage), and
    the compute type is float32, or bfloat16 with an even stack (pairs of
    planes share a 32-bit word)."""
    batch, _, height, width = obs_shape
    dtype = jnp.dtype(out_dtype)
    return (lane_order(batch, seq_window) is not None
            and height % _SUBLANE_ROWS == 0 and width == LANES
            and (dtype == jnp.float32
                 or (dtype == jnp.bfloat16 and frame_stack % 2 == 0)))


_MAX_TILES = 5       # lane tiles a grid step emits at most


def _tiles_per_step(steps: int) -> int:
    """Lane tiles one grid step emits: the largest divisor of the steps up
    to ``_MAX_TILES``. A pixel's words of one output block are one run in
    HBM, 1 KiB a lane tile, and longer runs write faster; past five tiles
    the larger blocks cost more at the pipeline's ends than the runs win
    (my chip runs, PR 26, the kernel alone: B128 x T55 0.849 / 0.798 /
    0.819 ms at 1 / 5 / 11 tiles, B64 x T125 0.913 / 0.873 / 0.889 / 0.897
    at 1 / 3 / 7 / 9), and would crowd the input out of VMEM."""
    return max(u for u in range(1, _MAX_TILES + 1) if steps % u == 0)


def _stack_kernel_lanes(geom, in_hbm, out_ref, inbuf, sem, *state):
    # Grid (column of 128 sequences, block of 32 stored rows, group of U
    # lane tiles), in order. A stored time step ("slot" j) is 128 frames
    # (rows r = s * batch_tile + b: every segment's step j); lane tile i's
    # plane k is slot i + k, so a grid step decodes slots g*U + K-1 ..
    # (g+1)*U + K-2 (the first one of a block also the K-1 before them),
    # each once, and emits a tile per slot from it and the K-1 slots kept
    # in VMEM (``state``: the last slot's rounded bits, and a ring of the
    # words older tiles still need). Slot j+1's rows arrive by DMA while
    # slot j is decoded. in_hbm: the whole (B, T+K-1, Hs, 128) uint8
    # window; out_ref: (32, W, U, K, 128), written as (32, W*U*Q, 128)
    # 32-bit words, Q sublanes a pixel and tile: the K float32 planes, or
    # K/2 pairs of bfloat16 planes (plane 2q in the low half).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (batch_tile, group, steps, tiles, row_len, frame_stack, out_height,
     out_width, packed, interpret) = geom
    col, hb, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    slots = steps + frame_stack - 1
    width8 = -(-out_width // 8) * 8
    planes = frame_stack // 2 if packed else frame_stack
    lag_step = 2 if packed else 1
    ring_len = (planes - 1) * lag_step
    if packed:
        prev, ring = state if ring_len else (state[0], None)
    else:
        prev, ring = None, (state[0] if ring_len else None)
    # the input block as 32-bit words: word row q of frame r holds stored
    # rows 4q..4q+3 (one a byte) of 128 pixels
    words = inbuf.bitcast(jnp.int32).reshape(2 * LANES * _WORD_ROWS, LANES)
    inv = jnp.float32(1.0 / 255.0)

    def segment_copy(j, s):
        # segment s's stored step of slot j; past the window: its last
        tau = jnp.minimum(s * steps + j, row_len - 1)
        return pltpu.make_async_copy(
            in_hbm.at[pl.ds(col * LANES, batch_tile), tau,
                      pl.ds(hb * _SUBLANE_ROWS, _SUBLANE_ROWS)],
            inbuf.at[j % 2, pl.ds(s * batch_tile, batch_tile)],
            sem.at[j % 2])

    def start(j):
        jax.lax.fori_loop(
            0, group, lambda s, c: (segment_copy(j, s).start(), c)[1], 0)

    def store_plane(h, p, tile, plane):
        # word plane p of pixel row h in lane tile ``tile`` of the block:
        # one sublane row of every pixel's (tiles * planes, 128) words, a
        # single strided store
        if not interpret:
            out_words = out_ref.bitcast(jnp.int32) if packed else out_ref
            out_words.reshape(_SUBLANE_ROWS, out_width * tiles * planes,
                              LANES)[
                h, pl.ds(tile * planes + p, out_width,
                         stride=tiles * planes), :] = plane
        elif packed:
            # the interpreter cannot write through a ref's view: the same
            # store, a half word at a time
            for e, half in enumerate((jax.lax.shift_left(plane, 16),
                                      jax.lax.bitwise_and(plane, -0x10000))):
                out_ref[h, :, tile, 2 * p + e, :] = (
                    jax.lax.bitcast_convert_type(half, jnp.float32)
                    .astype(out_ref.dtype))
        else:
            out_ref[h, :, tile, p, :] = plane

    def slot(j, carry):
        @pl.when(j == 0)
        def _():
            start(j)

        @pl.when(j + 1 < slots)
        def _():
            start(j + 1)

        jax.lax.fori_loop(
            0, group, lambda s, c: (segment_copy(j, s).wait(), c)[1], 0)
        # the tile this slot completes; the block's first K-1 slots
        # complete none and write where the next one overwrites
        tile = jnp.maximum(j - (frame_stack - 1) - g * tiles, 0)

        def word_row(q, carry):
            # 128 frames' word row q, frames on sublanes -> frames on lanes
            x = words[pl.ds((j % 2) * (LANES * _WORD_ROWS) + q, LANES,
                            stride=_WORD_ROWS), :]
            x = x.T[:width8]                              # (W, 128 frames)
            for byte in range(4):
                h = q * 4 + byte
                level = jax.lax.bitwise_and(
                    jax.lax.shift_right_logical(x, 8 * byte), 0xFF)
                # normalise in f32, round once into the compute type (the
                # reference's /255 then cast; inside a jit XLA turns that
                # divide into this multiply as well)
                val = level.astype(jnp.float32) * inv
                if packed:
                    # round to nearest even into the high half by hand:
                    # the low and high plane of a pair meet in one word
                    bits = jax.lax.bitcast_convert_type(val, jnp.int32)
                    bits = bits + 0x7FFF + jax.lax.bitwise_and(
                        jax.lax.shift_right_logical(bits, 16), 1)
                    item = jax.lax.bitwise_or(
                        jax.lax.bitwise_and(bits, -0x10000), prev[h])
                    prev[h] = jax.lax.shift_right_logical(bits, 16)
                else:
                    item = val
                for p in range(planes):
                    lag = (planes - 1 - p) * lag_step
                    plane = item if lag == 0 else ring[
                        (j + ring_len - lag) % ring_len, h]
                    store_plane(h, p, tile, plane[:out_width])
                if ring_len:
                    ring[j % ring_len, h] = item
            return carry

        word_rows = -(-out_height // 4)
        jax.lax.fori_loop(
            0, jnp.minimum(_WORD_ROWS, word_rows - hb * _WORD_ROWS),
            word_row, 0)
        return carry

    jax.lax.fori_loop(
        jnp.where(g == 0, 0, g * tiles + frame_stack - 1),
        (g + 1) * tiles + frame_stack - 1, slot, 0)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def stack_frames_lanes(obs: jnp.ndarray, seq_window: int, frame_stack: int,
                       interpret: bool = False, out_dtype=jnp.float32,
                       out_height=None, out_width=None) -> LaneFrames:
    """The decode into the first convolution's own layout: frames in lanes,
    the K planes in the tile's sublanes. Takes what ``lanes_route`` admits.

    The kernel writes (H, W, tile, K, 128): frame n = tile * 128 + lane of
    ``lane_order`` in lanes, a pixel's K planes in the sublanes under it.
    Seen as (n, h, w, k) those are the bytes of the TPU's layout of the
    convolution's input, minor to major N, K, W, H with tile (4, 128) and
    two bf16 rows to a 32-bit word, so XLA makes a bitcast of the transpose
    below and puts no copy between the kernel and the convolution."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, row_len, height, width = obs.shape
    out_height = height if out_height is None else out_height
    out_width = width if out_width is None else out_width
    assert lanes_route(obs.shape, seq_window, frame_stack, out_dtype)
    columns, group, steps = lane_order(batch, seq_window)
    tiles = _tiles_per_step(steps)
    batch_tile = batch // columns
    packed = jnp.dtype(out_dtype).itemsize == 2
    planes = frame_stack // 2 if packed else frame_stack
    width8 = -(-out_width // 8) * 8
    ring_len = (planes - 1) * (2 if packed else 1)
    plane_block = (_SUBLANE_ROWS, width8, LANES)
    state = ([pltpu.VMEM(plane_block, jnp.int32)] if packed else []) + (
        [pltpu.VMEM((ring_len,) + plane_block,
                    jnp.int32 if packed else out_dtype)] if ring_len else [])
    itemsize = jnp.dtype(out_dtype).itemsize
    block_words = _SUBLANE_ROWS * width8 * LANES
    out_block_bytes = (_SUBLANE_ROWS * out_width * tiles * frame_stack * LANES
                       * itemsize)
    out_bytes = (out_height * out_width * columns * steps * frame_stack
                 * LANES * itemsize)
    # two output blocks, two input blocks, the kept slots, and room for
    # Mosaic's own; no more, so that XLA can keep the input in VMEM too
    vmem_bytes = (2 * out_block_bytes + 2 * LANES * _SUBLANE_ROWS * width
                  + 4 * block_words * (ring_len + packed) + 4 * 2**20)
    kernel = functools.partial(
        _stack_kernel_lanes,
        (batch_tile, group, steps, tiles, seq_window + frame_stack - 1,
         frame_stack, out_height, out_width, packed, interpret))
    words = pl.pallas_call(
        kernel,
        grid=(columns, -(-out_height // _SUBLANE_ROWS), steps // tiles),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (_SUBLANE_ROWS, out_width, tiles, frame_stack, LANES),
            lambda c, hb, g: (hb, 0, c * (steps // tiles) + g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (out_height, out_width, columns * steps, frame_stack, LANES),
            out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, LANES, _SUBLANE_ROWS, width), obs.dtype),
            pltpu.SemaphoreType.DMA((2,))] + state,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem_bytes),
        # what XLA's memory-space assignment goes by: told that this call
        # moves bytes and does no arithmetic, it keeps the gather's output
        # (this call's input) in VMEM, where there is room beside the
        # kernel's own blocks, and the gather writes half its HBM traffic
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=obs.size + out_bytes),
        interpret=interpret,
    )(obs)
    frames = words.transpose(2, 4, 0, 1, 3).reshape(
        -1, out_height, out_width, frame_stack)
    return LaneFrames(frames, batch, seq_window)


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


def decode_route(obs_shape, seq_window: int, frame_stack: int,
                 use_pallas: bool = False, out_dtype=jnp.float32) -> str:
    """The decode ``stack_frames`` takes for this input, by name:
    "reference" (jnp), "lanes" (the first convolution's own layout, where
    ``lanes_route`` admits the input) or "planar" (the Pallas kernel for
    every other shape)."""
    if not use_pallas:
        return "reference"
    if lanes_route(obs_shape, seq_window, frame_stack, out_dtype):
        return "lanes"
    return "planar"


def stack_frames(obs: jnp.ndarray, seq_window: int, frame_stack: int,
                 use_pallas: bool = False,
                 out_dtype=jnp.float32,
                 out_height=None,
                 out_width=None):
    """Dispatch by ``decode_route``: pallas on TPU when requested, jnp
    otherwise. Returns the (B, T, H, W, K) array, or on the "lanes" route
    ``LaneFrames`` of that logical shape."""
    route = decode_route(obs.shape, seq_window, frame_stack, use_pallas,
                         out_dtype)
    if route == "lanes":
        return stack_frames_lanes(obs, seq_window, frame_stack,
                                  out_dtype=out_dtype, out_height=out_height,
                                  out_width=out_width)
    if route == "planar":
        return stack_frames_pallas(obs, seq_window, frame_stack,
                                   out_dtype=out_dtype, out_height=out_height,
                                   out_width=out_width)
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
    compiles/wins is a TPU measurement (``k_gather_roofline`` of the
    benchmark's learner cells); interpret mode pins the semantics either
    way."""
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


# ---------------------------------------------------------------------------
# Rows summed to their positions (the held experts of the cores that route: a
# chunk of (position, expert) pairs' rows goes back to the positions' sum,
# models/cores/experts.py ``held_experts_ffn``).

# Positions a grid step of ``add_rows_pallas`` accumulates. My chip runs,
# PR 30, the kernel alone at the cell's sizes, three chunks: 0.716 / 0.707 /
# 0.696 / 0.690 ms at 256 / 512 / 1,000 / 2,000; the smaller block leaves
# the fast memory to the kernel's neighbours.
_ADD_ROWS_BLOCK = 512
# Bytes of a call's rows, which it keeps whole in VMEM as float32 (a dynamic
# sublane of a packed bf16 array is not something Mosaic reads); with the
# pipeline's four blocks of the sums (16 MiB at 2,048 wide) and Mosaic's own,
# under a v5e's 128 MiB. 9,216 rows at 2,048 wide: the first chunk of either
# cell with experts (6,656 | 8,704 rows: 52 | 68 MiB) is one call, and more
# rows than that go through in slices, each streaming the sums once.
_ADD_ROWS_VMEM_BYTES = 72 * 2**20


def add_rows_reference(acc: jnp.ndarray, rows: jnp.ndarray,
                       pos: jnp.ndarray) -> jnp.ndarray:
    """The jnp twin of ``add_rows_pallas``: one scatter-add."""
    return acc.at[pos].add(rows.astype(acc.dtype), mode="drop")


def sum_rows_reference(rows: jnp.ndarray, pos: jnp.ndarray,
                       positions: int) -> jnp.ndarray:
    """The jnp twin of ``sum_rows_pallas``."""
    return add_rows_reference(
        jnp.zeros((positions, rows.shape[1]), jnp.float32), rows, pos)


def _rows_to_positions(acc, n: int, rows, pos, block: int, interpret: bool,
                       slice_rows: int):
    """``add_rows_pallas`` (``acc`` (n, d)) and ``sum_rows_pallas`` (``acc``
    None: the sums start at zero and no array of zeros is made or read)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = rows.shape[1]
    if not slice_rows:
        slice_rows = max(_ADD_ROWS_VMEM_BYTES // (4 * d) // 8 * 8, 8)
    if rows.shape[0] > slice_rows:
        for lo in range(0, rows.shape[0], slice_rows):
            acc = _rows_to_positions(
                acc, n, rows[lo:lo + slice_rows], pos[lo:lo + slice_rows],
                block, interpret, slice_rows)
        return acc
    if rows.shape[0] % 8:
        pad = -rows.shape[0] % 8
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        pos = jnp.pad(pos, (0, pad), constant_values=n)
    count = rows.shape[0]
    block = min(block, -(-n // 8) * 8)
    blocks = -(-n // block)
    assert (blocks * block + 1) * count < 2**31, "the sort's key is 32 bits"
    # rows in the order of their positions; the rows that stand for no
    # pair sort past the last block and no step reaches them
    key = jnp.sort(jnp.where(pos < n, pos, blocks * block) * count
                   + jnp.arange(count, dtype=jnp.int32))
    source, where = key % count, key // count
    starts = jnp.searchsorted(
        where, jnp.arange(blocks + 1, dtype=jnp.int32) * block,
        side="left").astype(jnp.int32)

    def kernel(source_ref, where_ref, starts_ref, *refs):
        rows_hbm, out_ref, rows_ref, sem = refs[-4:]
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _():
            copy = pltpu.make_async_copy(rows_hbm, rows_ref, sem)
            copy.start()
            copy.wait()

        out_ref[...] = (jnp.zeros_like(out_ref) if acc is None
                        else refs[0][...])

        def add(j, carry):
            p = where_ref[j] - b * block
            out_ref[pl.ds(p, 1), :] += rows_ref[pl.ds(source_ref[j], 1), :]
            return carry

        jax.lax.fori_loop(starts_ref[b], starts_ref[b + 1], add, 0)

    streamed = pl.BlockSpec((block, d), lambda b, *_: (b, 0))
    held = [] if acc is None else [acc]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks,),
            in_specs=[streamed] * len(held) + [
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=streamed,
            scratch_shapes=[pltpu.VMEM((count, d), jnp.float32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        input_output_aliases={3: 0} if held else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the pipeline's two blocks in and two out, the chunk's rows,
            # and room for Mosaic's own
            vmem_limit_bytes=(4 * block + count) * d * 4 + 4 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=count * d, transcendentals=0,
            bytes_accessed=((1 + len(held)) * n + count) * d * 4),
        name="add_rows",
        interpret=interpret,
    )(source, where, starts, *held, rows.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def add_rows_pallas(acc: jnp.ndarray, rows: jnp.ndarray, pos: jnp.ndarray,
                    block: int = _ADD_ROWS_BLOCK,
                    interpret: bool = False,
                    slice_rows: int = 0) -> jnp.ndarray:
    """``acc[pos[r]] += rows[r]`` in float32 for every row with ``pos[r]``
    under ``acc``'s length; a row at or past it (a caller's mark for "no
    pair here") is not read. acc (N, d) float32, updated in place; rows
    (R, d); pos (R,) int32, repeats allowed.

    A scatter as a gather: the rows are numbered in the order of their
    positions (one sort of R keys, the row's number in the key's low
    digits, so equal positions keep the rows' order), the positions are cut
    into blocks of ``block``, and a grid step holds one block of ``acc`` in
    VMEM and adds its rows to it, one dynamic sublane at a time, from the
    chunk's rows, which the first step brought into VMEM whole. ``acc``
    streams through once (the pipeline's blocks) for every ``slice_rows``
    rows (what ``_ADD_ROWS_VMEM_BYTES`` holds, where none is given): 131 MB
    at the cell's 8,000 x 2,048, where XLA's scatter-add of 2,560 rows takes
    three times as long and the gather of all pairs' rows it replaces
    (2.9 ms for three chunks' worth) four times (my chip runs, PR 30)."""
    return _rows_to_positions(acc, acc.shape[0], rows, pos, block, interpret,
                              slice_rows)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def sum_rows_pallas(rows: jnp.ndarray, pos: jnp.ndarray, positions: int,
                    block: int = _ADD_ROWS_BLOCK,
                    interpret: bool = False,
                    slice_rows: int = 0) -> jnp.ndarray:
    """``add_rows_pallas`` onto sums that start at zero, (positions, d)
    float32: the same kernel with no sums to read, so the walk's first
    chunk writes its positions' sums once and neither fills nor reads an
    array of zeros (half of the call's traffic at the cell's sizes)."""
    return _rows_to_positions(None, positions, rows, pos, block, interpret,
                              slice_rows)


def add_rows(acc: jnp.ndarray, rows: jnp.ndarray,
             pos: jnp.ndarray) -> jnp.ndarray:
    """``acc[pos[r]] += rows[r]``, rows with ``pos[r] >= len(acc)`` left
    out: the Pallas kernel in a program lowered for a TPU, its jnp twin in
    one lowered for anything else (Mosaic compiles for the TPU alone)."""
    return jax.lax.platform_dependent(
        acc, rows, pos, tpu=add_rows_pallas, default=add_rows_reference)


def sum_rows(rows: jnp.ndarray, pos: jnp.ndarray,
             positions: int) -> jnp.ndarray:
    """``add_rows`` onto (positions, d) float32 sums that start at zero."""
    return jax.lax.platform_dependent(
        rows, pos,
        tpu=functools.partial(sum_rows_pallas, positions=positions),
        default=functools.partial(sum_rows_reference, positions=positions))


# ---------------------------------------------------------------------------
# Grouped products (the held experts' SwiGLU of the cores that route,
# models/cores/experts.py: rows sorted by expert, each group of rows times
# its expert's matrix, and the weights' gradient back).

# What the blocks of a grouped product may take of a v5e's 128 MiB of VMEM:
# the pipeline's two buffers of each block (``_grouped_vmem`` adds the
# product's float32 result and room for Mosaic's own).
_GROUPED_VMEM_BYTES = 64 * 2**20
# Rows of a grouped product's row tile, and of the tile of a call whose rows
# are no multiple of it (acting's 384 pairs at 64 lanes).
GROUPED_ROW_TILE = 256
# Parts a row tile that groups share is cut into: a visit takes only the
# parts that hold rows of its group.
_GROUPED_PARTS = 2
# Rows from which a call takes the kernel. Its code is larger than that of
# XLA's product, and every call site's is traced, lowered and loaded at each
# start whether it ever runs: with the kernel at all 140 sites of a cell's
# step the executable was 460 MB against 394 and the warm set-up 8 s longer
# (my chip runs, PR 37). The walk's overflow chunks (1,024 rows, in loops and
# branches that no logged step has entered) and acting's chunk (256 rows) are
# most of the sites and little of the work: they keep XLA's product.
_GROUPED_MIN_ROWS = 2048
# Columns of a block that one product inside the kernel takes
# (``_each_column_chunk``).
_GROUPED_COLUMNS = 512
_GROUPED_ROW_TILES = (GROUPED_ROW_TILE, GROUPED_ROW_TILE // 2)
# (M, k) x (M, n) -> (G, k, n): the rows are the contracted axis, cut into
# the groups (what XLA's own backward of ``ragged_dot`` asks for)
_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def grouped_matmul_reference(rows, weights, group_sizes, transposed=False,
                             out_dtype=None):
    """The ``jax.lax`` twin of ``grouped_matmul_pallas``."""
    if transposed:
        weights = jnp.swapaxes(weights, 1, 2)
    return jax.lax.ragged_dot(rows, weights, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(out_dtype or rows.dtype)


def grouped_outer_reference(rows, cots, group_sizes):
    """The ``jax.lax`` twin of ``grouped_outer_pallas``."""
    return jax.lax.ragged_dot_general(
        rows, cots, group_sizes, _ROWS_CONTRACTED,
        preferred_element_type=jnp.float32)


def _running_sum(counts):
    """The inclusive running sum of a few int32 counts, as one sum over a
    (n, n) table: a scan of eight numbers is a loop of its own in the TPU's
    program, and XLA fuses nothing into it."""
    index = jnp.arange(counts.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(index[None, :] <= index[:, None],
                             counts[None, :], 0), axis=1)


def group_visits(group_sizes, rows: int, tile: int, outer: bool = False):
    """The walk of a grouped product over ``rows`` sorted rows in tiles of
    ``tile``: (tile, group, first row, end row) of each visit and the visits'
    number, each (visits,) int32 but the last, (1,). A tile is visited once
    for every group that has rows in it, in the rows' order, with that
    group's rows (``[first, end)``, numbered over all rows: the visit's are
    those of them that lie in its tile); so a tile that two groups share is
    visited twice and ``rows // tile + groups - 1`` visits always suffice.
    The visits past the last repeat it, so a step that makes one of them
    moves no block. Where the product writes rows (``outer`` false) the
    tiles wholly past the groups' total are visited once each, for no rows:
    they are written as zeros. Where it writes a block a group (``outer``) a
    group without rows is visited once, for no rows, and those tiles are
    not."""
    groups, tiles = group_sizes.shape[0], rows // tile
    # a few dozen visits of a few groups: sums over small tables in place of
    # scans, searches and gathers, so that XLA makes a few small fusions of
    # the walk and merges the walks of the products of one chunk
    ends = jnp.minimum(_running_sum(group_sizes.astype(jnp.int32)), rows)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = starts // tile
    count = jnp.where(ends > starts, (ends - 1) // tile - first + 1,
                      int(outer))
    before = _running_sum(count)
    live_tiles = (ends[-1] + tile - 1) // tile
    total = before[-1] + (0 if outer else tiles - live_tiles)
    visit = jnp.minimum(jnp.arange(tiles + groups - 1, dtype=jnp.int32),
                        total - 1)
    group = jnp.sum(visit[:, None] >= before[None, :], axis=1,
                    dtype=jnp.int32)
    past = group >= groups              # a tile past the groups' total
    group = jnp.minimum(group, groups - 1)
    mine = group[:, None] == jnp.arange(groups, dtype=jnp.int32)[None, :]

    def of_group(values):
        return jnp.sum(jnp.where(mine, values[None, :], 0), axis=1)

    at = jnp.where(past, live_tiles + visit - before[-1],
                   of_group(first) + visit - of_group(before - count))
    return (jnp.clip(at, 0, tiles - 1), group,
            jnp.where(past, 0, of_group(starts)),
            jnp.where(past, 0, of_group(ends)), total[None])


def tile_rows_visited(group_sizes, tile: int):
    """Rows of the row tiles a grouped product visits for these groups at
    tiles of ``tile`` rows (visits x tile; a tile two groups share counts
    twice): the pairs over it is how full the MXU's row tiles are."""
    sizes = group_sizes.astype(jnp.int32)
    ends = _running_sum(sizes)
    return tile * jnp.sum(jnp.where(
        sizes > 0, (ends - 1) // tile - (ends - sizes) // tile + 1, 0))


def _shared_tile_parts(tm: int, base, lo, hi):
    """The parts a row tile of ``tm`` rows from row ``base`` on is cut into
    where groups share it: of each, (its rows' slice of the tile, which of
    them lie in ``[lo, hi)`` (rows, 1), whether any does)."""
    rows = tm // _GROUPED_PARTS if tm % (8 * _GROUPED_PARTS) == 0 else tm
    for at in range(0, tm, rows):
        row = base + at + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        yield (slice(at, at + rows), (row >= lo) & (row < hi),
               (hi > base + at) & (lo < base + at + rows))


def _each_column_chunk(columns: int, body) -> None:
    """``body(chunk)`` for the chunks of ``_GROUPED_COLUMNS`` columns of a
    block ``columns`` wide, in a loop, and for what is left over: the
    kernel's code is one chunk's product and not the whole block's, which
    Mosaic unrolls: with whole blocks a cell's step program, 140 such calls,
    was 736 MB where the parent's was 394 (CPU, count) and took 12 s longer
    to load (my chip runs, PR 37); with chunks of 512 columns it is 460 MB
    and the nine products of a layer cost 5% more (1,024: 1-3%, 256: 7-8%)."""
    from jax.experimental import pallas as pl
    whole = columns // _GROUPED_COLUMNS

    def step(j, carry):
        body(pl.ds(pl.multiple_of(j * _GROUPED_COLUMNS, _GROUPED_COLUMNS),
                   _GROUPED_COLUMNS))
        return carry

    if whole:
        jax.lax.fori_loop(0, whole, step, 0)
    if columns % _GROUPED_COLUMNS:
        body(slice(whole * _GROUPED_COLUMNS, columns))


def _grouped_vmem(result: int, *blocks) -> int:
    """The pipeline's two buffers of every block, the product's float32
    ``result`` and room for Mosaic's own."""
    return 2 * sum(blocks) + result + 4 * 2**20


@functools.partial(jax.jit, static_argnames=(
    "transposed", "out_dtype", "tiles", "interpret"))
def grouped_matmul_pallas(rows, weights, group_sizes, transposed=False,
                          out_dtype=None, tiles=None, interpret=False):
    """Rows sorted by group times their group's matrix: rows (M, k), weights
    (G, k, n), or (G, n, k) ``transposed`` (contracted over their last axis:
    the weights as they are kept serve the backward), ``group_sizes`` (G,) ->
    (M, n) in ``out_dtype`` (the rows' where none is given), accumulated in
    float32; rows past the groups' total are zeros.

    ``tiles`` = (row tile, column tile). The grid is (column tiles, visits),
    the visits of ``group_visits`` innermost: a step takes one row tile whole
    in k and its group's (k, column tile) block of the weights, whose block
    index stays while that group's row tiles pass, so the weights are read
    once a column tile and the rows once for each. A tile that lies inside
    one group is one product and one store; one that groups share is visited
    for each, and keeps the other groups' rows as they are (the block stays
    in VMEM between visits that follow each other)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), groups = rows.shape, weights.shape[0]
    n = weights.shape[1 if transposed else 2]
    out_dtype = jnp.dtype(out_dtype or rows.dtype)
    tm, tn = tiles or grouped_tiles(m, k, n, rows.dtype.itemsize,
                                    out_dtype.itemsize)
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    walk = group_visits(group_sizes, m, tm)
    contract = (((1,), (1 if transposed else 0,)), ((), ()))

    def kernel(tile_ref, group_ref, lo_ref, hi_ref, total_ref, x_ref, w_ref,
               o_ref):
        v = pl.program_id(1)
        tile, lo, hi = tile_ref[v], lo_ref[v], hi_ref[v]
        base = tile * tm
        real = v < total_ref[0]
        whole = (lo <= base) & (hi >= base + tm)

        def product(part, columns):
            weights = w_ref[columns, :] if transposed else w_ref[:, columns]
            return jax.lax.dot_general(
                x_ref[part, :], weights, contract,
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

        @pl.when(real & whole)
        def _():
            def write(columns):
                o_ref[:, columns] = product(slice(None), columns)
            _each_column_chunk(tn, write)

        @pl.when(real & jnp.logical_not(whole))
        def _():
            # a tile that groups share, or that no group reaches: of each
            # part of it that holds rows of this group, those rows; the
            # others keep what an earlier visit wrote, or are zeros at the
            # tile's first
            first = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile)
            for part, mine, reached in _shared_tile_parts(tm, base, lo, hi):
                @pl.when(reached)
                def _():
                    def write(columns):
                        kept = o_ref[part, columns]
                        kept = jnp.where(first, jnp.zeros_like(kept), kept)
                        o_ref[part, columns] = jnp.where(
                            mine, product(part, columns), kept)
                    _each_column_chunk(tn, write)

                @pl.when(jnp.logical_not(reached) & first)
                def _():
                    o_ref[part, :] = jnp.zeros_like(o_ref[part, :])

    w_block = ((None, tn, k) if transposed else (None, k, tn))
    w_index = ((lambda j, v, t, g, *_: (g[v], j, 0)) if transposed
               else (lambda j, v, t, g, *_: (g[v], 0, j)))
    size = rows.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, m // tm + groups - 1),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, t, *_: (t[v], 0)),
                      pl.BlockSpec(w_block, w_index)],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_grouped_vmem(
                tm * tn * 4, tm * k * size, k * tn * size,
                tm * tn * out_dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(n // tn * m * k + groups * k * n) * size
            + m * n * out_dtype.itemsize),
        name="grouped_matmul",
        interpret=interpret,
    )(*walk, rows, weights)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def grouped_outer_pallas(rows, cots, group_sizes, tiles=None,
                         interpret=False):
    """The gradient of a grouped product's weights: each group's rows (M, k)
    transposed times its rows of ``cots`` (M, n) -> (G, k, n) float32, as the
    MXU accumulates it; a group without rows gives zeros.

    ``tiles`` = (row tile, column tile). The grid is (column tiles, visits):
    a step takes one row tile of both operands and adds its product to its
    group's (k, column tile) block of the result, which stays in VMEM while
    that group's row tiles pass and is written once; of a tile that groups
    share each visit takes its own group's rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n, groups = rows.shape, cots.shape[1], group_sizes.shape[0]
    tm, tn = tiles or grouped_tiles(m, k, n, rows.dtype.itemsize, 4,
                                    outer=True)
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    walk = group_visits(group_sizes, m, tm, outer=True)

    def kernel(tile_ref, group_ref, lo_ref, hi_ref, total_ref, x_ref, c_ref,
               o_ref):
        v = pl.program_id(1)
        lo, hi = lo_ref[v], hi_ref[v]
        base = tile_ref[v] * tm
        real = v < total_ref[0]

        @pl.when((v == 0)
                 | (group_ref[jnp.maximum(v - 1, 0)] != group_ref[v]))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        def add(x, part, mine=None):
            def to(columns):
                c = c_ref[part, columns]
                if mine is not None:
                    c = jnp.where(mine, c, jnp.zeros_like(c))
                o_ref[:, columns] += jax.lax.dot_general(
                    x, c, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            _each_column_chunk(tn, to)

        whole = (lo <= base) & (hi >= base + tm)

        @pl.when(real & whole)
        def _():
            add(x_ref[...], slice(None))

        @pl.when(real & jnp.logical_not(whole))
        def _():
            # a tile that groups share: this group's rows of each part of
            # it that holds any
            for part, mine, reached in _shared_tile_parts(tm, base, lo, hi):
                @pl.when(reached)
                def _():
                    x = x_ref[part, :]
                    add(jnp.where(mine, x, jnp.zeros_like(x)), part, mine)

    size = rows.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, m // tm + groups - 1),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, t, *_: (t[v], 0)),
                      pl.BlockSpec((tm, tn), lambda j, v, t, *_: (t[v], j))],
            out_specs=pl.BlockSpec((None, k, tn),
                                   lambda j, v, t, g, *_: (g[v], 0, j))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_grouped_vmem(
                k * tn * 4, tm * k * size, tm * tn * size, k * tn * 4)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(n // tn * m * k + m * n) * size
            + groups * k * n * 4),
        name="grouped_outer",
        interpret=interpret,
    )(*walk, rows, cots)


def grouped_tiles(m: int, k: int, n: int, itemsize: int, out_itemsize: int,
                  outer: bool = False):
    """(row tile, column tile) of a grouped product of (m, k) rows by
    (k, n) matrices (``outer``: of the (k, n) float32 result a group), from
    the shapes and the operands' bytes alone, or None where the kernel does
    not tile them (the caller then takes ``jax.lax.ragged_dot``): rows that
    are no multiple of a row tile, a k or n that does not fill the lanes,
    blocks that ``_GROUPED_VMEM_BYTES`` does not hold.

    The row tile is 256 rows, 128 where the rows are no multiple of 256
    (my chip runs, PR 37, the products alone at both cells' shapes, groups
    of ~730 | ~975 rows: 256 and 128 read within 3% of each other, 512
    10-20% slower: with groups this small nearly every tile of 512 is
    shared). The contraction is whole in one step, and the column tile the
    widest divisor of n in whole lanes whose blocks fit: the block that
    stays in VMEM while a group's row tiles pass (the group's weights, or
    its float32 result) is then fetched or written once, and the rows are
    read once for every column tile (the widest read fastest: the first
    product 0.548 ms at 2,816 columns, 0.567 at 1,408, 0.66 and more at 256
    and 512; XLA's kernel 1.121)."""
    tm = next((t for t in _GROUPED_ROW_TILES if m % t == 0), None)
    if tm is None or k % 128 or n % 128:
        return None
    for tn in range(n, 0, -128):
        blocks = (tm * k * itemsize
                  + (k * tn * 4 + tm * tn * itemsize if outer
                     else k * tn * itemsize + tm * tn * out_itemsize))
        if n % tn == 0 and 2 * blocks <= _GROUPED_VMEM_BYTES:
            return tm, tn
    return None


def grouped_matmul(rows, weights, group_sizes, transposed=False,
                   out_dtype=None):
    """Rows (M, k) sorted by group times their group's matrix of ``weights``
    (G, k, n), or (G, n, k) ``transposed``, -> (M, n) in ``out_dtype``,
    accumulated in float32; the rows past the groups' total are undefined.
    The Pallas kernel in a program lowered for a TPU where the shapes give
    it tiles (``grouped_tiles``) and there are ``_GROUPED_MIN_ROWS`` rows or
    more, ``jax.lax.ragged_dot`` in one lowered for anything else and for
    the other shapes."""
    out_dtype = jnp.dtype(out_dtype or rows.dtype)
    n = weights.shape[1 if transposed else 2]
    tiles = grouped_tiles(*rows.shape, n, rows.dtype.itemsize,
                          out_dtype.itemsize)
    reference = functools.partial(grouped_matmul_reference,
                                  transposed=transposed, out_dtype=out_dtype)
    if tiles is None or rows.shape[0] < _GROUPED_MIN_ROWS:
        return reference(rows, weights, group_sizes)
    return jax.lax.platform_dependent(
        rows, weights, group_sizes, default=reference,
        tpu=functools.partial(grouped_matmul_pallas, transposed=transposed,
                              out_dtype=out_dtype, tiles=tiles))


def grouped_outer(rows, cots, group_sizes):
    """Each group's rows (M, k) transposed times its rows of ``cots`` (M, n)
    -> (G, k, n) float32: the Pallas kernel or ``jax.lax.ragged_dot_general``
    as ``grouped_matmul`` chooses."""
    tiles = grouped_tiles(*rows.shape, cots.shape[1], rows.dtype.itemsize, 4,
                          outer=True)
    if tiles is None or rows.shape[0] < _GROUPED_MIN_ROWS:
        return grouped_outer_reference(rows, cots, group_sizes)
    return jax.lax.platform_dependent(
        rows, cots, group_sizes, default=grouped_outer_reference,
        tpu=functools.partial(grouped_outer_pallas, tiles=tiles))
