"""On-chip pallas kernel gate: compile + parity-check every kernel on the
REAL Mosaic pipeline in one command.

    python -m r2d2_tpu.cli.chip_checks            # all kernels
    python -m r2d2_tpu.cli.chip_checks --only decode

Interpret-mode tests (the CPU suite) pin each kernel's semantics but
cannot catch Mosaic lowering rejections — historically the dominant
failure class (uint8->f32 cast, non-tile-aligned HBM slices, bf16
minor-dim insertion, strided-store width: all discovered only on chip).
This gate runs each kernel at a small but TILE-FAITHFUL shape (every
constraint the production shape exercises — uint8 (32,128) storage
tiles, 84x84 true frames under padded storage, bf16 compute — is
preserved) and checks bit/tolerance parity against the jnp twin, so a
lowering regression surfaces in minutes instead of mid-bench.

One PASS/FAIL line per case; a FAIL carries the first line of the
compiler's (or the assertion's) message.
Exit code: 0 = all pass, 1 = any FAIL, 2 = no accelerator.
"""

import sys
import time


def _check(name, fn):
    t0 = time.time()
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — report and continue
        # the first lines that say something (numpy's assertion text
        # opens with a blank line; a Mosaic error leads with its verdict)
        lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
        msg = " | ".join(lines[:4])[:400] if lines else type(e).__name__
        print(f"FAIL {name} ({time.time()-t0:.1f}s): {type(e).__name__}: "
              f"{msg}")
        return False
    print(f"PASS {name} ({time.time()-t0:.1f}s)")
    return True


def run_chip_checks(only: str = "") -> int:
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()

    import numpy as np

    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    print(f"backend: {devs[0].platform} ({devs[0].device_kind})")
    if devs[0].platform == "cpu":
        print("chip_checks needs an accelerator backend (pallas kernels "
              "do not lower on CPU); the CPU suite's interpret-mode tests "
              "cover semantics", file=sys.stderr)
        return 2

    checks = []

    def fresh_rng():
        # per case, so --only selects cases without changing their data
        return np.random.default_rng(0)

    def add(name, fn):
        if only in name:
            checks.append((name, fn))

    # --- obs decode (stack_frames), standard + padded-storage strip ------
    def decode():
        rng = fresh_rng()
        from r2d2_tpu.ops.pallas_kernels import (stack_frames_pallas,
                                                 stack_frames_reference)
        obs = jnp.asarray(rng.integers(0, 255, (4, 60, 84, 84)), jnp.uint8)
        for dtype in (jnp.float32, jnp.bfloat16):
            got = stack_frames_pallas(obs, 55, 4, out_dtype=dtype)
            want = stack_frames_reference(obs, 55, 4, out_dtype=dtype)
            # the kernel multiplies by 1/255 where the reference divides:
            # equal after the bf16 rounding, one ulp apart in f32 (the
            # CPU suite's interpret-mode test carries the same bound)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=2e-7 if dtype == jnp.float32 else 0.0, atol=0.0)
    add("decode", decode)

    def decode_padded():
        rng = fresh_rng()
        from r2d2_tpu.ops.pallas_kernels import (stack_frames_pallas,
                                                 stack_frames_reference)
        obs = jnp.asarray(rng.integers(0, 255, (2, 60, 96, 128)), jnp.uint8)
        got = stack_frames_pallas(obs, 55, 4, out_dtype=jnp.bfloat16,
                                  out_height=84, out_width=84)
        want = stack_frames_reference(obs, 55, 4, out_dtype=jnp.bfloat16,
                                      out_height=84, out_width=84)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    add("decode_padded_strip", decode_padded)

    # the TPU path's decode (frames in lanes, the first convolution's own
    # layout) at the cells' shapes, the reference check's and float32
    def decode_lanes(batch, window, dtype):
        def check():
            rng = fresh_rng()
            from r2d2_tpu.ops.pallas_kernels import (stack_frames_lanes,
                                                     stack_frames_reference)
            obs = jnp.asarray(
                rng.integers(0, 256, (batch, window + 3, 96, 128)), jnp.uint8)
            got = stack_frames_lanes(obs, window, 4, False, dtype, 84, 84)
            seq = got.sequence(got.frames.reshape(got.frames.shape[0], -1))
            want = stack_frames_reference(obs, window, 4, dtype, 84, 84)
            np.testing.assert_allclose(
                np.asarray(seq, np.float32).reshape(want.shape),
                np.asarray(want, np.float32),
                rtol=2e-7 if dtype == jnp.float32 else 0.0, atol=0.0)
        return check
    add("decode_lanes_b128_t55", decode_lanes(128, 55, jnp.bfloat16))
    add("decode_lanes_b64_t125", decode_lanes(64, 125, jnp.bfloat16))
    add("decode_lanes_b8_t55", decode_lanes(8, 55, jnp.bfloat16))
    add("decode_lanes_b128_t55_f32", decode_lanes(128, 55, jnp.float32))

    # --- replay window gathers ------------------------------------------
    def row_gather():
        rng = fresh_rng()
        from r2d2_tpu.ops.pallas_kernels import (gather_rows_pallas,
                                                 gather_rows_reference)
        ring = jnp.asarray(rng.integers(0, 255, (8, 60, 84, 84)), jnp.uint8)
        bi = jnp.asarray(rng.integers(0, 8, (16,)), jnp.int32)
        st = jnp.asarray(rng.integers(0, 5, (16,)), jnp.int32)
        got = gather_rows_pallas(ring, bi, st, 55)
        want = gather_rows_reference(ring, bi, st, 55)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    add("row_gather", row_gather)

    def exact_gather():
        rng = fresh_rng()
        from r2d2_tpu.ops.pallas_kernels import (gather_rows_exact_pallas,
                                                 gather_rows_reference)
        # padded-storage tile shape (96, 128): the Mosaic alignment this
        # kernel exists for
        ring = jnp.asarray(rng.integers(0, 255, (8, 60, 96, 128)), jnp.uint8)
        bi = jnp.asarray(rng.integers(0, 8, (16,)), jnp.int32)
        st = jnp.asarray(rng.integers(0, 5, (16,)), jnp.int32)
        got = gather_rows_exact_pallas(ring, bi, st, 55)
        want = gather_rows_reference(ring, bi, st, 55)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    add("exact_gather", exact_gather)

    # --- rows summed to their positions (the held experts of the cores
    # that route, models/cores/experts.py) at the moonlight-core cell's
    # shapes (8,000 positions, chunks of 2,560 bf16 rows) and the lfm2-core
    # cell's (chunks of 3,072), at acting's (T = 1: 64 lanes, one chunk of
    # 64 x 6 pairs), at sizes that fill no tile, and since PR 34 at the
    # walk's first chunk in either cell (6,656 | 8,704 rows, float32 in
    # VMEM: 52 | 68 MiB; ``sum_rows``: its sums start at zero), at an
    # overflow chunk's 1,024 rows and at more rows than one call holds (two
    # slices)
    def add_rows(positions, rows, dtype, from_zero=False):
        def check():
            rng = fresh_rng()
            from r2d2_tpu.ops.pallas_kernels import (add_rows_pallas,
                                                     add_rows_reference,
                                                     sum_rows_pallas)
            # a third of the rows stand for no pair
            pos = jnp.asarray(np.where(
                rng.random(rows) < 0.33, positions,
                rng.integers(0, positions, rows)), jnp.int32)
            x = jnp.asarray(rng.standard_normal((rows, 2048)), dtype)
            acc = (jnp.zeros((positions, 2048)) if from_zero else
                   jnp.asarray(rng.standard_normal((positions, 2048)),
                               jnp.float32))
            want = add_rows_reference(acc, x, pos)
            got = (sum_rows_pallas(x, pos, positions) if from_zero
                   else add_rows_pallas(acc, x, pos))
            # float32 sums of at most a few rows, in another order
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)
        return check
    add("add_rows_n8000_r2560", add_rows(8000, 2560, jnp.bfloat16))
    add("add_rows_n8000_r3072", add_rows(8000, 3072, jnp.bfloat16))
    add("add_rows_n64_r384", add_rows(64, 384, jnp.bfloat16))
    add("add_rows_n13_r78_f32", add_rows(13, 78, jnp.float32))
    add("sum_rows_n8000_r6656", add_rows(8000, 6656, jnp.bfloat16, True))
    add("sum_rows_n8000_r8704", add_rows(8000, 8704, jnp.bfloat16, True))
    add("sum_rows_n64_r384", add_rows(64, 384, jnp.bfloat16, True))
    add("add_rows_n8000_r1024", add_rows(8000, 1024, jnp.bfloat16))
    add("add_rows_n8000_r12800_sliced", add_rows(8000, 12800, jnp.bfloat16))

    # --- the held experts' grouped products (ISSUE 37), the three forms at
    # the cells' first chunks (6,656 x 2,048 x 2,816, 8,704 x 2,048 x 3,584:
    # the first product, the one against the weights' last axis, the
    # weights' gradient), the second product's float32 out, an overflow
    # chunk's 1,024 rows and acting's 384, through the tiles the shapes
    # give (``grouped_tiles``) against ``jax.lax.ragged_dot``: bf16 operands,
    # groups 0.9 full that end inside tiles, one of them empty
    def grouped(form, rows, k, n, out_dtype=jnp.bfloat16):
        def check():
            rng = fresh_rng()
            from r2d2_tpu.ops import pallas_kernels as pk
            share = rng.dirichlet(np.full(7, 60.0))
            sizes = np.floor(share * 0.9 * rows).astype(np.int32)
            sizes = jnp.asarray(np.insert(sizes, 3, 0), jnp.int32)
            live = int(sizes.sum())
            x = jnp.asarray(rng.standard_normal((rows, k)), jnp.bfloat16)
            if form == "outer":
                cots = jnp.asarray(rng.standard_normal((rows, n)),
                                   jnp.bfloat16)
                assert pk.grouped_tiles(rows, k, n, 2, 4, True)
                got = pk.grouped_outer_pallas(x, cots, sizes)
                want = pk.grouped_outer_reference(x, cots, sizes)
                assert not np.asarray(got[3]).any()
            else:
                last = form == "weights_last"
                w = jnp.asarray(rng.standard_normal(
                    (8, n, k) if last else (8, k, n)) * 0.02, jnp.bfloat16)
                assert pk.grouped_tiles(rows, k, n, 2,
                                        jnp.dtype(out_dtype).itemsize)
                got = pk.grouped_matmul_pallas(x, w, sizes, transposed=last,
                                               out_dtype=out_dtype)
                want = pk.grouped_matmul_reference(x, w, sizes, last,
                                                   out_dtype)
                assert got.dtype == out_dtype
                assert not np.asarray(got[live:], np.float32).any()
                got, want = got[:live], want[:live]
            # float32 sums of k bf16 products in another order, then (the
            # rows' forms) one rounding to bf16 on either side
            got, want = (np.asarray(a, np.float32) for a in (got, want))
            scale = float(np.abs(want).max())
            np.testing.assert_allclose(
                got, want, rtol=0,
                atol=scale * (2e-2 if out_dtype == jnp.bfloat16 else 1e-4))
        return check
    for cell, rows, width in (("moonlight", 6656, 1408),
                              ("lfm2", 8704, 1792)):
        add(f"grouped_{cell}_gate_up_r{rows}",
            grouped("weights", rows, 2048, 2 * width))
        add(f"grouped_{cell}_down_f32_r{rows}",
            grouped("weights", rows, width, 2048, jnp.float32))
        add(f"grouped_{cell}_down_last_axis_r{rows}",
            grouped("weights_last", rows, 2048, width, jnp.float32))
        add(f"grouped_{cell}_gate_up_last_axis_r{rows}",
            grouped("weights_last", rows, 2 * width, 2048))
        add(f"grouped_{cell}_outer_gate_up_r{rows}",
            grouped("outer", rows, 2048, 2 * width))
        add(f"grouped_{cell}_outer_down_r{rows}",
            grouped("outer", rows, width, 2048))
    add("grouped_overflow_chunk_r1024",
        grouped("weights", 1024, 2048, 2816))
    add("grouped_overflow_chunk_outer_r1024",
        grouped("outer", 1024, 2048, 2816))
    add("grouped_acting_r384", grouped("weights", 384, 2048, 2816))
    add("grouped_acting_down_f32_r256",
        grouped("weights", 256, 1792, 2048, jnp.float32))

    # --- quantized acting forward (ISSUE 14): compile + parity ----------
    def quant_forward():
        rng = fresh_rng()
        # The int8 forward has no pallas kernel, but it is the first
        # program that streams int8 weights + per-channel scales through
        # the bf16 MXU matmul path — the compile itself (int8 dequant
        # fusion, mixed f32 LSTM carry under bf16 torso/head) is what
        # this cell validates on the real toolchain, plus tolerance
        # parity and greedy agreement against the f32 twin.
        from r2d2_tpu.actor.policy import make_forward_fn
        from r2d2_tpu.config import NetworkConfig
        from r2d2_tpu.models.network import (NetworkApply,
                                             make_inference_bundle)
        ncfg = NetworkConfig(inference_dtype="int8")
        net = NetworkApply(6, ncfg, 4, 84, 84)
        params = net.init(jax.random.PRNGKey(0))
        bundle = jax.device_get(make_inference_bundle(net, params, 1))
        obs = rng.random((16, 84, 84, 4)).astype(np.float32)
        la = rng.integers(0, 6, 16).astype(np.int32)
        hid = rng.standard_normal((16, 2, 512)).astype(np.float32) * 0.1
        qfwd = make_forward_fn(net, probe_interval=1)
        a_q, q_q, h_q, probe = qfwd(bundle, obs, la, hid, np.int32(0),
                                    np.int32(16))
        f32fwd = make_forward_fn(net, "f32")
        a_f, q_f, h_f = f32fwd(params, obs, la, hid)
        dq, agree, probed = (float(np.asarray(x)) for x in probe)
        assert probed == 1.0, "probe branch did not fire at tick 0"
        scale = max(float(np.abs(np.asarray(q_f)).max()), 1e-3)
        assert float(np.abs(np.asarray(q_q) - np.asarray(q_f)).max()) \
            / scale < 0.05, "quantized Q diverges > 5% of Q range"
        host_agree = float(np.mean(np.asarray(a_q) == np.asarray(a_f)))
        assert agree >= 0.9 and host_agree >= 0.9, \
            f"greedy agreement {agree:.3f}/{host_agree:.3f} < 0.9"
        # the recurrent carry must come back f32 (drift containment)
        assert np.asarray(h_q).dtype == np.float32
    add("quant_forward", quant_forward)

    if not checks:
        print(f"no checks match --only={only!r}", file=sys.stderr)
        return 2
    ok = all([_check(name, fn) for name, fn in checks])
    return 0 if ok else 1


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    only = ""
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--only="):
            only = a.split("=", 1)[1]
        elif a == "--only" and i + 1 < len(argv):
            i += 1
            only = argv[i]
        elif a in ("-h", "--help"):
            print(__doc__)
            return 0
        else:
            print(f"unknown arg {a!r} (supported: --only SUBSTR)",
                  file=sys.stderr)
            return 2
        i += 1
    return run_chip_checks(only)


if __name__ == "__main__":
    sys.exit(main())
