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
    reference scale) strips the lane pad in BOTH pallas kernels (planar
    and nhwc) and the reference twin, matching an unpadded decode
    exactly."""
    from r2d2_tpu.ops.pallas_kernels import stack_frames_pallas_nhwc
    B, T, K, H, W = 2, 5, 3, 12, 12
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1, H, W)), jnp.uint8)
    obs_pad = jnp.pad(obs, ((0, 0), (0, 0), (0, 4), (0, 6)))  # -> (16, 18)
    want = np.asarray(stack_frames_reference(obs, T, K))
    got_ref = np.asarray(stack_frames_reference(obs_pad, T, K,
                                                out_height=H, out_width=W))
    got_pl = np.asarray(stack_frames_pallas(obs_pad, T, K, True,
                                            out_height=H, out_width=W))
    got_nhwc = np.asarray(stack_frames_pallas_nhwc(obs_pad, T, K, True,
                                                   out_height=H, out_width=W))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_allclose(got_pl, want, rtol=2e-7)
    np.testing.assert_allclose(got_nhwc, want, rtol=2e-7)
    assert got_pl.shape == got_nhwc.shape == (B, T, H, W, K)


def test_stack_frames_nhwc_matches_reference(rng):
    """The NHWC-emitting decode (K interleaved into the lane dim in-kernel,
    no post-kernel transpose) matches the reference twin — including with
    a padded storage height and bf16 output."""
    from r2d2_tpu.ops.pallas_kernels import stack_frames_pallas_nhwc
    B, T, K, H, W = 3, 6, 4, 12, 16
    obs = jnp.asarray(rng.integers(0, 255, (B, T + K - 1 + 2, H, W)),
                      jnp.uint8)
    want = np.asarray(stack_frames_reference(obs, T, K))
    got = np.asarray(stack_frames_pallas_nhwc(obs, T, K, True))
    assert got.shape == (B, T, H, W, K)
    np.testing.assert_allclose(got, want, rtol=2e-7)

    obs_pad = jnp.pad(obs, ((0, 0), (0, 0), (0, 4), (0, 0)))
    got_pad = np.asarray(stack_frames_pallas_nhwc(obs_pad, T, K, True,
                                                  out_height=H))
    np.testing.assert_allclose(got_pad, want, rtol=2e-7)

    want_bf16 = np.asarray(stack_frames_reference(obs, T, K,
                                                  out_dtype=jnp.bfloat16))
    got_bf16 = np.asarray(stack_frames_pallas_nhwc(obs, T, K, True,
                                                   out_dtype=jnp.bfloat16))
    np.testing.assert_array_equal(got_bf16, want_bf16)


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


# ---------------------------------------------------------------------------
# Fused LSTM time-scan (ops/pallas_lstm.py)


def _lstm_inputs(rng, T=7, B=8, H=128, dtype=jnp.float32):
    xpb = jnp.asarray(rng.standard_normal((T, B, 4 * H)), dtype)
    wh = jnp.asarray(rng.standard_normal((H, 4 * H)) * 0.1, dtype)
    c0 = jnp.asarray(rng.standard_normal((B, H)), dtype)
    h0 = jnp.asarray(rng.standard_normal((B, H)), dtype)
    return xpb, wh, c0, h0


def test_lstm_scan_pallas_forward_matches_reference(rng):
    """f32 interpret-mode forward is bit-exact vs the lax.scan twin (the
    kernel's f32 carry + f32 gate math reproduce the scan exactly when
    nothing is rounded)."""
    from r2d2_tpu.ops.pallas_lstm import (lstm_scan_pallas,
                                          lstm_scan_reference)
    args = _lstm_inputs(rng)
    hs_r, (cf_r, hf_r) = lstm_scan_reference(*args)
    hs_p, (cf_p, hf_p) = lstm_scan_pallas(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(hs_p), np.asarray(hs_r))
    np.testing.assert_array_equal(np.asarray(cf_p), np.asarray(cf_r))
    np.testing.assert_array_equal(np.asarray(hf_p), np.asarray(hf_r))


def test_lstm_scan_pallas_grads_match_reference(rng):
    """custom-VJP backward kernel vs jax.grad of the scan twin, for every
    input — including the final-carry cotangents (the loss reads c_fin and
    h_fin so dcfin/dhfin are non-zero)."""
    from r2d2_tpu.ops.pallas_lstm import (lstm_scan_pallas,
                                          lstm_scan_reference)
    args = _lstm_inputs(rng)
    T, B, H = args[0].shape[0], args[0].shape[1], args[1].shape[0]
    w = jnp.asarray(rng.standard_normal((T, B, H)), jnp.float32)

    def loss(fn, args):
        hs, (c, h) = fn(*args)
        return jnp.sum(hs * w) + jnp.sum(c * 1.3) + jnp.sum(h * 0.7)

    g_ref = jax.grad(lambda a: loss(lstm_scan_reference, a))(args)
    g_pal = jax.grad(lambda a: loss(
        lambda *a: lstm_scan_pallas(*a, interpret=True), a))(args)
    for name, a, b in zip(("dxpb", "dwh", "dc0", "dh0"), g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=2e-6, err_msg=name)


def test_lstm_scan_pallas_unused_carry_grads(rng):
    """When the loss ignores the final carry JAX feeds zero cotangents for
    it; the kernel must still produce the right dxpb/dwh."""
    from r2d2_tpu.ops.pallas_lstm import (lstm_scan_pallas,
                                          lstm_scan_reference)
    args = _lstm_inputs(rng, T=4, B=8, H=128)

    def loss(fn, args):
        hs, _ = fn(*args)
        return jnp.sum(hs ** 2)

    g_ref = jax.grad(lambda a: loss(lstm_scan_reference, a))(args)
    g_pal = jax.grad(lambda a: loss(
        lambda *a: lstm_scan_pallas(*a, interpret=True), a))(args)
    for name, a, b in zip(("dxpb", "dwh", "dc0", "dh0"), g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=2e-6, err_msg=name)


def test_hoisted_lstm_pallas_path_matches_scan(rng):
    """HoistedLSTM(use_pallas=True) plumbing — bias folding, axis swaps,
    carry order — against the default scan path, same params. The bias
    fold changes one f32 addition order, hence allclose not array_equal."""
    from r2d2_tpu.models.network import HoistedLSTM
    B, T, D, H = 4, 6, 48, 128
    xs = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    carry = (jnp.asarray(rng.standard_normal((B, H)), jnp.float32),
             jnp.asarray(rng.standard_normal((B, H)), jnp.float32))
    scan_cell = HoistedLSTM(features=H)
    params = scan_cell.init(jax.random.PRNGKey(0), carry, xs)
    # make the bias nonzero so the fold is actually exercised
    params = jax.tree_util.tree_map(lambda x: x, params)
    params["params"]["bias"] = jnp.asarray(
        rng.standard_normal((4 * H,)) * 0.1, jnp.float32)
    (c_s, h_s), out_s = scan_cell.apply(params, carry, xs)
    pallas_cell = HoistedLSTM(features=H, use_pallas=True,
                              pallas_interpret=True)
    (c_p, h_p), out_p = pallas_cell.apply(params, carry, xs)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_s),
                               atol=1e-5, rtol=1e-5)


def test_hoisted_lstm_pallas_single_step_falls_back(rng):
    """T=1 (the actor's step shape) must stay on the scan path — the
    pallas kernel is a sequence fusion, not a step dispatch."""
    from r2d2_tpu.models.network import HoistedLSTM
    B, D, H = 4, 48, 128
    xs = jnp.asarray(rng.standard_normal((B, 1, D)), jnp.float32)
    carry = (jnp.zeros((B, H)), jnp.zeros((B, H)))
    cell = HoistedLSTM(features=H, use_pallas=True, pallas_interpret=False)
    params = cell.init(jax.random.PRNGKey(0), carry, xs)
    # pallas_interpret=False would fail to compile on CPU if the kernel
    # were (wrongly) taken; succeeding proves the fallback
    (_, _), out = cell.apply(params, carry, xs)
    assert out.shape == (B, 1, H)


def test_lstm_scan_pallas_bf16_tracks_reference(rng):
    """bf16 interpret-mode pass of both kernels (the dtype the chip runs
    under the shipped policy): forward within bf16 tolerance of the f32
    reference, and the custom-VJP pipeline produces finite, same-scale
    grads for every input. Catches dtype-specific kernel bugs (bad casts,
    f32-only ops) before the on-chip A/B."""
    from r2d2_tpu.ops.pallas_lstm import (lstm_scan_pallas,
                                          lstm_scan_reference)
    f32args = _lstm_inputs(rng, T=5, B=8, H=128)
    args = tuple(a.astype(jnp.bfloat16) for a in f32args)
    hs_r, (cf_r, hf_r) = lstm_scan_reference(*f32args)
    hs_p, (cf_p, hf_p) = lstm_scan_pallas(*args, interpret=True)
    assert hs_p.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(hs_p, np.float32),
                               np.asarray(hs_r), atol=0.03, rtol=0.03)
    np.testing.assert_allclose(np.asarray(cf_p, np.float32),
                               np.asarray(cf_r), atol=0.05, rtol=0.05)

    def loss(a):
        hs, (c, h) = lstm_scan_pallas(*a, interpret=True)
        return (jnp.sum(hs.astype(jnp.float32) ** 2)
                + jnp.sum(c.astype(jnp.float32))
                + jnp.sum(h.astype(jnp.float32)))

    g_pal = jax.grad(loss)(args)

    def loss_ref(a):
        hs, (c, h) = lstm_scan_reference(*a)
        return jnp.sum(hs ** 2) + jnp.sum(c) + jnp.sum(h)

    g_ref = jax.grad(loss_ref)(f32args)
    for name, a, b in zip(("dxpb", "dwh", "dc0", "dh0"), g_pal, g_ref):
        a = np.asarray(a, np.float32)
        b = np.asarray(b)
        assert np.isfinite(a).all(), name
        assert a.dtype == np.float32 and a.shape == b.shape
        # same magnitude ballpark (bf16 rounding both in the kernel and in
        # the bf16 reference chain rules out elementwise equality)
        denom = max(np.abs(b).max(), 1e-3)
        assert np.abs(a - b).max() / denom < 0.25, name


@pytest.mark.slow
def test_lstm_scan_pallas_block_t_matches_reference(rng):
    """block_t > 1 (several timesteps per grid iteration) must be exactly
    the same computation: bit-exact f32 forward across block boundaries,
    grads to f32 epsilon — including the in-block h_prev recomputation
    (o*tanh(c)) and the block-boundary carry handoff."""
    from r2d2_tpu.ops.pallas_lstm import (lstm_scan_pallas,
                                          lstm_scan_reference)
    args = _lstm_inputs(rng, T=10, B=8, H=128)
    hs_r, (cf_r, hf_r) = lstm_scan_reference(*args)
    w = jnp.asarray(rng.standard_normal(hs_r.shape), jnp.float32)

    def loss(fn, a):
        hs, (c, h) = fn(*a)
        return jnp.sum(hs * w) + jnp.sum(c * 1.3) + jnp.sum(h * 0.7)

    g_ref = jax.grad(lambda a: loss(lstm_scan_reference, a))(args)
    for bt in (2, 5, 10):
        hs_p, (cf_p, hf_p) = lstm_scan_pallas(*args, interpret=True,
                                              block_t=bt)
        np.testing.assert_array_equal(np.asarray(hs_p), np.asarray(hs_r),
                                      err_msg=f"block_t={bt}")
        np.testing.assert_array_equal(np.asarray(cf_p), np.asarray(cf_r))
        g_pal = jax.grad(lambda a: loss(
            lambda *x: lstm_scan_pallas(*x, interpret=True, block_t=bt),
            a))(args)
        for name, a, b in zip(("dxpb", "dwh", "dc0", "dh0"), g_ref, g_pal):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-6, rtol=3e-6,
                                       err_msg=f"{name} block_t={bt}")


def test_lstm_scan_pallas_block_t_must_divide(rng):
    from r2d2_tpu.ops.pallas_lstm import lstm_scan_pallas
    args = _lstm_inputs(rng, T=7, B=8, H=128)
    with pytest.raises(ValueError, match="divide"):
        lstm_scan_pallas(*args, interpret=True, block_t=3)
