"""Pallas kernel tests.

Interpret mode runs on the suite's CPU mesh; the compiled-lowering gate
(test_stack_frames_pallas_compiled_on_tpu) runs the real Mosaic pipeline in
a subprocess with the CPU pin stripped, and skips when no TPU is attached —
so lowering regressions (like round 2's unsupported uint8 cast, which
interpret mode cannot catch) surface in any TPU-attached pytest run instead
of only in the driver bench."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.ops.pallas_kernels import (
    gather_rows_pallas, gather_rows_reference, resolve_pallas_obs_decode,
    stack_frames_pallas, stack_frames_reference)


def test_stack_frames_pallas_matches_reference(rng):
    B, T, K, H, W = 3, 7, 4, 12, 16
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1 + 2, H, W)),
                      jnp.uint8)  # +2: row longer than the window, like replay
    want = np.asarray(stack_frames_reference(obs, T, K))
    got = np.asarray(stack_frames_pallas(obs, T, K, True))
    assert got.shape == (B, T, H, W, K)
    # kernel multiplies by 1/255 (one VPU op) vs the reference's divide —
    # identical up to one ulp
    np.testing.assert_allclose(got, want, rtol=2e-7)
    assert got.dtype == np.float32
    assert got.max() <= 1.0 and got.min() >= 0.0


def test_gather_rows_exact_matches_reference(rng):
    """The exact-read async-copy gather (interpret mode) returns the same
    windows as the vmapped dynamic-slice twin."""
    from r2d2_tpu.ops.pallas_kernels import gather_rows_exact_pallas
    ring = jnp.asarray(rng.integers(0, 255, (8, 50, 16, 16)), jnp.uint8)
    bi = jnp.asarray(rng.integers(0, 8, (6,)), jnp.int32)
    st = jnp.asarray(rng.integers(0, 40, (6,)), jnp.int32)
    got = gather_rows_exact_pallas(ring, bi, st, 10, True)
    want = gather_rows_reference(ring, bi, st, 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stack_frames_out_height_strips_padding(rng):
    """out_height (exact-gather padded storage) strips the sublane pad in
    both decode twins, matching an unpadded decode exactly."""
    B, T, K, H, W = 2, 5, 3, 12, 16
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1, H, W)), jnp.uint8)
    obs_pad = jnp.pad(obs, ((0, 0), (0, 0), (0, 4), (0, 0)))  # H 12 -> 16
    want = np.asarray(stack_frames_reference(obs, T, K))
    got_ref = np.asarray(stack_frames_reference(obs_pad, T, K, out_height=H))
    got_pl = np.asarray(stack_frames_pallas(obs_pad, T, K, True,
                                            out_height=H))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_allclose(got_pl, want, rtol=2e-7)


def test_stack_frames_out_width_strips_padding(rng):
    """out_width (exact-gather lane-tile padding, 84x84 -> 96x128 at
    reference scale) strips the lane pad in the planar pallas kernel and
    the reference twin, matching an unpadded decode exactly."""
    B, T, K, H, W = 2, 5, 3, 12, 12
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1, H, W)), jnp.uint8)
    obs_pad = jnp.pad(obs, ((0, 0), (0, 0), (0, 4), (0, 6)))  # -> (16, 18)
    want = np.asarray(stack_frames_reference(obs, T, K))
    got_ref = np.asarray(stack_frames_reference(obs_pad, T, K,
                                                out_height=H, out_width=W))
    got_pl = np.asarray(stack_frames_pallas(obs_pad, T, K, True,
                                            out_height=H, out_width=W))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_allclose(got_pl, want, rtol=2e-7)
    assert got_pl.shape == (B, T, H, W, K)


def test_stack_frames_bf16_output(rng):
    """out_dtype=bf16 (the bf16-policy decode): both twins normalize in f32
    and round ONCE at the end, so kernel and reference agree bit-exactly
    and match an explicit f32->bf16 cast of the f32 result."""
    B, T, K, H, W = 2, 5, 3, 12, 16
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1, H, W)), jnp.uint8)
    ref_f32 = stack_frames_reference(obs, T, K)
    ref_bf16 = np.asarray(stack_frames_reference(obs, T, K,
                                                 out_dtype=jnp.bfloat16))
    got = np.asarray(stack_frames_pallas(obs, T, K, True,
                                         out_dtype=jnp.bfloat16))
    assert got.dtype == jnp.bfloat16 and ref_bf16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got, ref_bf16)
    np.testing.assert_array_equal(
        ref_bf16, np.asarray(ref_f32.astype(jnp.bfloat16)))


def test_stack_frames_reference_window_semantics(rng):
    """out[b, t, :, :, k] must be frame t+k (the learner-side obs_idx gather,
    ref worker.py:310,330)."""
    B, T, K, H, W = 1, 4, 2, 6, 6
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1, H, W)), jnp.uint8)
    out = np.asarray(stack_frames_reference(obs, T, K))
    for t in range(T):
        for k in range(K):
            np.testing.assert_allclose(
                out[0, t, :, :, k], np.asarray(obs[0, t + k], np.float32) / 255.0)


# --- the frame-in-lanes decode (the first convolution's own layout) ------

_JIT_REFERENCE = jax.jit(stack_frames_reference,
                         static_argnums=(1, 2, 3, 4, 5))

# (B, T, K, H, W, stored H, stored W): storage tile-padded like the exact
# gather's ring (84x84 held as 96x128 in the cells; small here)
_LANES_CASES = {
    "aligned-b128": (128, 3, 4, 24, 24, 32, 128),
    "ragged-b64": (64, 5, 4, 24, 20, 32, 128),      # 320 frames: 2.5 tiles
    "check-b8": (8, 5, 4, 24, 24, 32, 128),         # the reference check's
    "twin-b4-k2": (4, 7, 2, 24, 24, 32, 128),       # the rehearsal twins'
    "two-row-blocks": (8, 6, 4, 40, 24, 64, 128),
    "two-columns-b256": (256, 2, 4, 12, 24, 32, 128),
    "many-tiles": (128, 12, 4, 12, 16, 32, 128),    # several tiles a step
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(_LANES_CASES))
def test_stack_frames_lanes_bit_equal_to_reference(rng, case, dtype):
    """The lanes kernel (interpret mode) is bit-equal to the reference
    decode as a jitted step runs it, once its frames are read back in
    sequence order: same levels, normalised in f32, rounded once."""
    from r2d2_tpu.ops.pallas_kernels import (LANES, lane_order, lanes_route,
                                             stack_frames_lanes)
    B, T, K, H, W, Hs, Ws = _LANES_CASES[case]
    obs = jnp.asarray(rng.integers(0, 256, (B, T + K - 1, Hs, Ws)), jnp.uint8)
    assert lanes_route(obs.shape, T, K, dtype)
    got = stack_frames_lanes(obs, T, K, True, dtype, H, W)
    columns, group, steps = lane_order(B, T)
    assert got.shape == (B, T, H, W, K)
    assert got.frames.shape == (columns * steps * LANES, H, W, K)
    assert got.frames.dtype == dtype
    rows = got.frames.reshape(got.frames.shape[0], -1)
    seq = got.sequence(rows).reshape(B, T, H, W, K)
    want = _JIT_REFERENCE(obs, T, K, dtype, H, W)
    np.testing.assert_array_equal(np.asarray(seq.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    # frames past the window pad the last lane tile with window frames:
    # finite, so a zero cotangent times them stays zero
    assert np.isfinite(np.asarray(got.frames.astype(jnp.float32))).all()


@pytest.mark.parametrize("batch,window", [(128, 55), (64, 125), (8, 55),
                                          (4, 7), (256, 3)])
def test_lane_order_is_a_permutation_of_the_window(batch, window):
    """Every (sequence, step) of the window has exactly one frame index in
    ``lane_order``, and ``LaneFrames.sequence`` reads it back."""
    from r2d2_tpu.ops.pallas_kernels import LANES, LaneFrames, lane_order
    columns, group, steps = lane_order(batch, window)
    n = columns * steps * LANES
    assert n >= batch * window and group * steps >= window
    assert (n - batch * window) < LANES * group   # under a tile a segment
    frames = jnp.zeros((n, 1, 1, 1), jnp.float32)
    ids = jnp.arange(n, dtype=jnp.float32)[:, None]
    back = np.asarray(LaneFrames(frames, batch, window).sequence(ids))[..., 0]
    assert back.shape == (batch, window)
    assert len(np.unique(back)) == batch * window
    bt = batch // columns
    c, i, s, b = np.unravel_index(back.astype(np.int64),
                                  (columns, steps, group, bt))
    np.testing.assert_array_equal(c * bt + b,
                                  np.arange(batch)[:, None] + 0 * back)
    np.testing.assert_array_equal(s * steps + i,
                                  np.arange(window)[None, :] + 0 * back)


@pytest.mark.parametrize("kwargs,want", [
    (dict(), "lanes"),
    (dict(batch=64, window=125), "lanes"),
    (dict(batch=8), "lanes"),
    (dict(batch=48), "planar"),                      # does not tile 128 lanes
    (dict(stored=(84, 84)), "planar"),               # storage not tile-padded
    (dict(stack=3), "planar"),                       # bf16 planes pair up
    (dict(stack=3, dtype=jnp.float32), "lanes"),
    (dict(dtype=jnp.float16), "planar"),
    (dict(use_pallas=False), "reference"),
])
def test_decode_route_follows_the_shapes(kwargs, want):
    """One path chosen by what the input shows, no knob."""
    from r2d2_tpu.ops.pallas_kernels import decode_route
    k = dict(batch=128, window=55, stack=4, stored=(96, 128),
             dtype=jnp.bfloat16, use_pallas=True)
    k.update(kwargs)
    route = decode_route(
        (k["batch"], k["window"] + k["stack"] - 1) + k["stored"],
        k["window"], k["stack"], k["use_pallas"], k["dtype"])
    assert route == want


def _jaxprs_in(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _count_eqns(jaxpr, name=None):
    """Equations of a jaxpr and everything nested in it (``name``: only
    those of that primitive)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += name is None or eqn.primitive.name == name
        n += sum(_count_eqns(sub, name) for sub in _jaxprs_in(eqn.params))
    return n


def _lanes_kernel_eqns(batch, window):
    from r2d2_tpu.ops.pallas_kernels import stack_frames_lanes
    obs = jax.ShapeDtypeStruct((batch, window + 3, 96, 128), jnp.uint8)
    outer = jax.make_jaxpr(
        lambda o: stack_frames_lanes(o, window, 4, False, jnp.bfloat16, 84,
                                     84).frames)(obs).jaxpr
    assert _count_eqns(outer, "pallas_call") == 1
    return _count_eqns(outer)


@pytest.mark.parametrize("batch,window", [(128, 125), (64, 55), (64, 125),
                                          (8, 55)])
def test_lanes_kernel_lowering_does_not_grow_with_the_window(batch, window):
    """The set-up budget as a test: what every process start traces and
    lowers is the same number of equations at T = 55 and T = 125, at B = 64
    and B = 128 (time, rows and segments loop in the grid or a fori_loop,
    never in Python)."""
    assert _lanes_kernel_eqns(batch, window) == _lanes_kernel_eqns(128, 55)


@pytest.mark.parametrize("batch,window", [(128, 55), (64, 125)])
def test_decode_inputs_builds_one_pallas_call(batch, window):
    """``k_decode_roofline`` counts every custom call under ``obs_decode``
    as one decode's bytes: the decode is one kernel, no helper."""
    import types

    from r2d2_tpu.learner.train_step import _decode_inputs
    from r2d2_tpu.ops.pallas_kernels import LaneFrames
    spec = types.SimpleNamespace(seq_window=window, frame_stack=4,
                                 frame_height=84, frame_width=84)
    net = types.SimpleNamespace(
        action_dim=6, module=types.SimpleNamespace(
            compute_dtype=jnp.bfloat16))
    seen = []

    def decode(obs, last_action):
        stacked, one_hot = _decode_inputs(
            net, spec, types.SimpleNamespace(obs=obs,
                                             last_action=last_action), True)
        seen.append(stacked)
        return stacked.frames, one_hot

    jaxpr = jax.make_jaxpr(decode)(
        jax.ShapeDtypeStruct((batch, window + 3, 96, 128), jnp.uint8),
        jax.ShapeDtypeStruct((batch, window), jnp.int32)).jaxpr
    assert isinstance(seen[0], LaneFrames)
    assert seen[0].shape == (batch, window, 84, 84, 4)
    assert _count_eqns(jaxpr, "pallas_call") == 1


@pytest.mark.parametrize("batch,use_double", [
    (8, False), (128, False), (8, True)], ids=["b8", "b128", "b8-double"])
def test_loss_through_lanes_decode_matches_reference_path(
        rng, monkeypatch, batch, use_double):
    """``make_loss_fn`` with the lanes kernel forced on (interpret mode)
    against the same loss on the jnp path: loss, priorities and gradients
    equal to f32 round-off. Pins the frame order that goes into the torso
    and comes back to the LSTM; under double-Q the target unroll decodes
    the window a second time."""
    import dataclasses

    import r2d2_tpu.ops.pallas_kernels as pk
    from r2d2_tpu.learner import create_train_state, make_loss_fn
    from r2d2_tpu.replay.device_replay import replay_sample
    from tests.test_train_step import OPT, _filled_replay, _net
    from tests.test_replay import make_spec

    spec = make_spec(batch_size=batch, exact_gather=True)
    assert (spec.stored_frame_height, spec.stored_frame_width) == (32, 128)
    net, _ = _net(spec, use_double=use_double)
    ts = create_train_state(jax.random.PRNGKey(2), net, OPT)
    target = net.init(jax.random.PRNGKey(77))
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # "pallas_exact_gather pads ..."
        rs = _filled_replay(spec, rng)
    sample = replay_sample(spec, rs, jax.random.PRNGKey(5))

    calls = []
    compiled = pk.stack_frames_lanes

    def interpreted(obs, seq_window, frame_stack, **kw):
        calls.append(obs.shape)
        return compiled(obs, seq_window, frame_stack, True, **kw)

    monkeypatch.setattr(pk, "stack_frames_lanes", interpreted)
    out = {}
    for decode in ("off", "on"):
        opt = dataclasses.replace(OPT, pallas_obs_decode=decode)
        loss_fn = make_loss_fn(net, spec, opt, use_double=use_double)
        out[decode] = jax.value_and_grad(loss_fn, has_aux=True)(
            ts.params, target, sample)
    assert len(calls) == (2 if use_double else 1)
    (loss_a, aux_a), grads_a = out["off"]
    (loss_b, aux_b), grads_b = out["on"]
    np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(aux_b["priorities"]),
                               np.asarray(aux_a["priorities"]),
                               rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_a),
                    jax.tree_util.tree_leaves(grads_b)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("batch,window,fill", [
    (128, (40, 10, 5), 1.0), (64, (40, 80, 5), 0.9921)],
    ids=["r2d2-ref", "r2d2-paper"])
def test_runtime_report_names_the_decode(batch, window, fill):
    """The engagement record: the route is static per shape, so it is a
    resolved fact of the run's first line and the benchmark's
    ``facts.resolved``."""
    from r2d2_tpu.config import Config
    from r2d2_tpu.utils.platform import runtime_report
    burn, learn, fwd = window
    tpu = {"network.bf16": "on", "optim.pallas_obs_decode": "on",
           "replay.pallas_sample_gather": "on",
           "replay.pallas_exact_gather": "on"}
    cfg = Config().replace(**{
        "replay.batch_size": batch, "sequence.burn_in_steps": burn,
        "sequence.learning_steps": learn, "sequence.forward_steps": fwd})
    resolved = runtime_report(cfg.replace(**tpu))["resolved"]
    assert resolved["decode_layout"] == "lanes"
    assert resolved["decode_lane_fill"] == fill
    resolved = runtime_report(cfg)["resolved"]       # this CPU: jnp decode
    assert resolved["decode_layout"] == "reference"
    assert resolved["decode_lane_fill"] is None


def test_gather_rows_pallas_matches_reference(rng):
    """Scalar-prefetch row gather (the replay-sample obs slice): interpret
    mode vs the vmapped dynamic-slice twin, including repeated rows and
    window starts at both row edges."""
    N, R, H, W = 5, 20, 12, 16
    WIN = 7
    ring = jnp.asarray(rng.integers(0, 255, (N, R, H, W)), jnp.uint8)
    block_idx = jnp.asarray([0, 3, 3, 4, 2, 0], jnp.int32)
    start = jnp.asarray([0, 5, 13, R - WIN, 1, 0], jnp.int32)
    want = np.asarray(gather_rows_reference(ring, block_idx, start, WIN))
    got = np.asarray(gather_rows_pallas(ring, block_idx, start, WIN, True))
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.uint8


def test_resolve_pallas_obs_decode():
    assert resolve_pallas_obs_decode("on") is True
    assert resolve_pallas_obs_decode("off") is False
    # the suite runs on the pinned CPU mesh, so auto resolves to the gather path
    assert resolve_pallas_obs_decode("auto") is False
    # legacy bool configs pass through
    assert resolve_pallas_obs_decode(True) is True
    with pytest.raises(ValueError):
        resolve_pallas_obs_decode("maybe")


_COMPILED_CHECK = """
import sys
import jax
if jax.default_backend() != "tpu":
    print("NOTPU")
    sys.exit(0)
import numpy as np
import jax.numpy as jnp
from r2d2_tpu.ops.pallas_kernels import stack_frames_pallas, stack_frames_reference
rng = np.random.default_rng(0)
obs = jnp.asarray(rng.integers(0, 255, (4, 58, 84, 84)).astype(np.uint8))
got = stack_frames_pallas(obs, 55, 4)          # interpret=False: real Mosaic
want = stack_frames_reference(obs, 55, 4)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-7)
from r2d2_tpu.ops.pallas_kernels import gather_rows_pallas, gather_rows_reference
ring = jnp.asarray(rng.integers(0, 255, (8, 412, 84, 84)).astype(np.uint8))
bi = jnp.asarray(rng.integers(0, 8, (16,)).astype(np.int32))
st = jnp.asarray(rng.integers(0, 412 - 58, (16,)).astype(np.int32))
got = gather_rows_pallas(ring, bi, st, 58)     # compiled scalar-prefetch path
want = gather_rows_reference(ring, bi, st, 58)
np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
print("OK")
"""


@pytest.mark.slow
def test_stack_frames_pallas_compiled_on_tpu():
    """Compiled-mode gate (VERDICT r2 #6): real Mosaic lowering at the bench's
    production shape, in a subprocess free of the suite's CPU-platform pin."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    # the only skip is "no TPU attached" (the check prints NOTPU); with a
    # chip present a hang or a failure is a failure
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILED_CHECK], env=env,
        capture_output=True, text=True, timeout=420)
    out = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and out and out[-1] == "NOTPU":
        pytest.skip("no TPU backend attached; compiled lowering not testable")
    assert proc.returncode == 0, (
        f"compiled pallas check failed (rc={proc.returncode}):\n{proc.stderr[-4000:]}")
    assert out and out[-1] == "OK"
