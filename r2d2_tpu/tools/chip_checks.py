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

import dataclasses
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
    # the keye-core cell's first chunk (16,384 positions x 8 x 16 / 128
    # pairs, 8% more, in tiles of 256) at its widths
    add("grouped_keye_gate_up_r17920", grouped("weights", 17920, 2048, 1536))
    add("grouped_keye_down_f32_r17920",
        grouped("weights", 17920, 768, 2048, jnp.float32))
    add("grouped_keye_down_last_axis_r17920",
        grouped("weights_last", 17920, 2048, 768, jnp.float32))
    add("grouped_keye_outer_down_r17920",
        grouped("outer", 17920, 768, 2048))
    add("grouped_overflow_chunk_r1024",
        grouped("weights", 1024, 2048, 2816))
    add("grouped_overflow_chunk_outer_r1024",
        grouped("outer", 1024, 2048, 2816))
    add("grouped_acting_r384", grouped("weights", 384, 2048, 2816))
    add("grouped_acting_down_f32_r256",
        grouped("weights", 256, 1792, 2048, jnp.float32))

    # --- the learned sparse attention's kernels (ops/sparse_attention.py)
    # at the keye-core cell's tiles (queries and keys in blocks of 512,
    # heads of 128, the indexer's 16 heads of 64 padded to 128 lanes, 8
    # query heads a key/value head) over a window of 2,048 positions and
    # 2,560 keys, against the plain jnp forms: the forward and both
    # backward kernels, the heads' probabilities, the indexer's scores and
    # their backward, the threshold's bisection against lax.top_k. bf16
    # operands on both sides: they differ by float32 sums in another order
    def sparse_kernels(part):
        def check():
            rng = fresh_rng()
            from r2d2_tpu.ops import sparse_attention as sa
            b, g, n, t, s, e, bq, bk = 1, 4, 8, 2048, 2560, 128, 512, 512
            offset = s - t
            bf = jnp.bfloat16

            def normal(*shape):
                return jnp.asarray(rng.standard_normal(shape), bf)

            def close(got, want, what, atol=2e-2):
                got, want = (np.asarray(x, np.float32) for x in (got, want))
                scale = max(float(np.abs(want).max()), 1e-30)
                np.testing.assert_allclose(got, want, rtol=0,
                                           atol=atol * scale, err_msg=what)

            pos = np.arange(s) - offset
            visible = pos[None, :] <= np.arange(t)[:, None]
            if part == "indexer":
                q, k = normal(b, 16, t, 64), normal(b, s, 64)
                w = jnp.asarray(rng.standard_normal((b, 16, t)), jnp.float32)
                mask = jnp.asarray(visible)[None]
                close(jnp.where(mask, sa.indexer_pallas(
                    q, w, k, offset, bq, bk), 0), jnp.where(
                        mask, sa.indexer_reference(q, w, k), 0), "scores")
                cot = jnp.where(mask, jnp.asarray(
                    rng.standard_normal((b, t, s)), jnp.float32), 0)
                want = jax.vjp(sa.indexer_reference, q, w, k)[1](cot)
                got = sa.indexer_back_pallas(q, w, k, cot, offset, bq, bk)
                for x, y, what in zip(got, want, ("dq", "dw", "dk")):
                    close(x, y, what)
                return
            if part == "select":
                x = jnp.where(jnp.asarray(visible)[None], jnp.asarray(
                    rng.standard_normal((b, t, s)), jnp.float32), -jnp.inf)
                want_k = jnp.minimum(jnp.asarray(visible.sum(-1))[None],
                                     2048).astype(jnp.int32)
                # a tie at the threshold in every eighth row
                value, _ = sa.kth_largest_reference(x, want_k, 2048)
                x = x.at[:, ::8, 600].set(value[:, ::8])
                got = sa.kth_largest_pallas(x, want_k)
                want = sa.kth_largest_reference(x, want_k, 2048)
                for g, w in zip(got, want):
                    assert np.array_equal(np.asarray(g), np.asarray(w)), \
                        "the bisection's threshold is not lax.top_k's"
                return
            q, k, v = normal(b, g * n, t, e), normal(b, g, s, e), \
                normal(b, g, s, e)
            # each query chooses its own key and a random 30% of the rest
            # of what it sees; a quarter of one block row chooses nothing
            pick = visible[None] & (rng.random((b, t, s)) < 0.3)
            pick |= pos[None, None, :] == np.arange(t)[None, :, None]
            d = jnp.where(jnp.asarray(pick), 0.5, -jnp.inf)
            scale = e ** -0.5
            o, lse = sa.sparse_attention_pallas(q, k, v, d, scale, bq, bk)
            o_ref, lse_ref = sa.attention_reference(q, k, v, d, scale)
            if part == "forward":
                close(o, o_ref, "o")
                close(lse, lse_ref, "lse", 1e-4)
                close(sa.chosen_probs_pallas(q, k, lse_ref, d, scale, bq, bk),
                      sa.chosen_probs_reference(q, k, lse_ref, d, scale),
                      "probs", 1e-3)
                return
            do = normal(b, g * n, t, e)
            dl = jnp.sum(do.astype(jnp.float32) * o_ref.astype(jnp.float32),
                         -1)
            want = jax.vjp(lambda q, k, v: sa.attention_reference(
                q, k, v, d, scale), q, k, v)[1](
                    (do, jnp.zeros_like(lse_ref)))
            got = sa.sparse_attention_back_pallas(q, k, v, d, do, lse_ref,
                                                  dl, scale, bq, bk)
            for x, y, what in zip(got, want, ("dq", "dk", "dv")):
                close(x, y, what)
        return check
    for part in ("forward", "backward", "indexer", "select"):
        add(f"sparse_attn_{part}", sparse_kernels(part))

    # --- the keye-core cell's comparison, at its timed sizes, as its runner
    # makes it (benchmarks/runners/learner_8k.py): the program's loss and Q
    # against the plain reference (reference/check.py), and its choice of
    # keys and indexer loss against the reference's, on two sequences of
    # its full ring; and two controls that must fail it: the same program
    # with the choice turned off (every query attends every key it sees)
    # and with its core's products on 8-bit (e4m3) operands. Each prints
    # its errors and the share of the reference's float32 choice of
    # (query, key) pairs it chose as well, per layer
    def keye_core():
        """The cases, sharing one preparation (the full-size network on its
        filled ring, two sampled sequences, the reference's outputs) that
        the first of them to run makes."""
        made = {}

        def network(core):
            from r2d2_tpu.models.network import NetworkApply
            cfg = made["cfg"]
            return NetworkApply(6, dataclasses.replace(cfg.network, core=core),
                                cfg.env.frame_stack, cfg.env.frame_height,
                                cfg.env.frame_width)

        def prepare():
            if made:
                return made
            import tempfile
            from benchmarks import harness, traffic
            from benchmarks.reference import check as reference_check
            from benchmarks.reference import r2d2_keye
            from r2d2_tpu.runtime.learner_loop import Learner
            bench = harness.load_benchmark()
            mix = harness.traffic_doc("learner-8k")
            cfg = harness.build_config(harness.program_overrides(
                harness.config_doc(bench, "keye-core"), mix),
                tempfile.mkdtemp(), 0)
            made["cfg"] = cfg
            net = network(cfg.network.core)
            learner = Learner(cfg, net, 0, seed=0)
            try:
                traffic.fill_ring(learner, 6, mix["parameters"]["replay"], 0)
                batch = reference_check.sample_sequences(learner, 2, 1)
                params, target = jax.device_get(
                    (learner.train_state.params,
                     learner.train_state.target_params))
            finally:
                learner.stop_background()
            fields = {f.name: getattr(batch, f.name)
                      for f in dataclasses.fields(batch)
                      if getattr(batch, f.name) is not None}
            made.update(
                net=net, batch=batch, fields=fields, params=params,
                target=target,
                spec=dataclasses.replace(learner.spec, batch_size=2),
                reference=jax.device_get(r2d2_keye.from_config(cfg)(
                    params, target, fields)))
            return made

        def compared(net, what):
            from benchmarks.reference import check as reference_check
            from benchmarks.runners import learner_8k
            from r2d2_tpu.learner.train_step import make_loss_fn
            m = prepare()
            loss, aux = jax.jit(make_loss_fn(net, m["spec"], m["cfg"].optim,
                                             True))(m["params"], m["target"],
                                                    m["batch"])
            program = jax.device_get({"loss": loss, **{
                k: aux[k] for k in ("priorities", "q_chosen", "abs_td")}})
            out = reference_check.compare(
                program, m["reference"], m["batch"].is_weights,
                reference_check.TOLERANCE["bfloat16"])
            chosen, kl = learner_8k.program_choice(
                net, m["spec"], m["params"], m["batch"], True)
            out = learner_8k.widen(out, learner_8k.compare_choice(
                chosen, kl, m["reference"], m["fields"], "bfloat16"))
            found = out["choice"]
            errors = ", ".join(
                f"{k} {v:.4g} (limit {part['limits'][k]:.4g})"
                for part in (out, found) for k, v in part["errors"].items())
            agreement = ", ".join(f"{x:.6f}"
                                  for x in found["choice_agreement"])
            print(f"  keye_core {what}: {errors}; the reference's pairs "
                  f"chosen too, per layer: {agreement}; indexer loss "
                  f"{found['indexer_loss_error']:.4g} off, per layer "
                  f"(program, reference): "
                  f"{found['indexer_loss_per_layer']}")
            return out["ok"], errors

        def sound():
            ok, errors = compared(prepare()["net"], "comparison")
            assert ok, errors

        def choice_off():
            core = prepare()["cfg"].network.core
            ok, _ = compared(network(dataclasses.replace(
                core, index_topk=1 << 20)), "choice off")
            assert not ok, "every key attended passed the comparison"

        def eight_bit():
            from r2d2_tpu.models.cores import experts, sparse_attn_moe
            from r2d2_tpu.ops import sparse_attention
            f8 = jnp.float8_e4m3fn

            def coarse(x, dtype):
                return x.astype(f8).astype(dtype)

            kept = (experts.matmul, experts.grouped_matmul,
                    sparse_attention.sparse_attention)

            def matmul(spec, x, w, dtype, out_dtype=None):
                return kept[0](spec, coarse(x, dtype), coarse(w, dtype),
                               dtype, out_dtype)

            def grouped(rows, weights, sizes, dtype, out_dtype=None,
                        transposed=False):
                return kept[1](coarse(rows, dtype), coarse(weights, dtype),
                               sizes, dtype, out_dtype, transposed)

            def attention(q, k, v, d, *rest):
                return kept[2](coarse(q, q.dtype), coarse(k, k.dtype),
                               coarse(v, v.dtype), d, *rest)

            core = prepare()["cfg"].network.core
            experts.matmul = sparse_attn_moe.matmul = matmul
            experts.grouped_matmul = grouped
            sparse_attention.sparse_attention = attention
            try:
                ok, _ = compared(network(core), "8-bit operands")
            finally:
                experts.matmul = sparse_attn_moe.matmul = kept[0]
                experts.grouped_matmul = kept[1]
                sparse_attention.sparse_attention = kept[2]
            assert not ok, "8-bit operands passed the comparison"

        return {"keye_core_sound": sound,
                "keye_core_choice_off_fails": choice_off,
                "keye_core_8bit_fails": eight_bit}
    for name, fn in keye_core().items():
        add(name, fn)

    # --- Kimi Delta Attention's chunked scan (ops/kda.py) at the
    # kimi-linear-core cell's tiles (heads of 128, chunks of 64) over a
    # window that is not whole chunks, against the per-position recurrence
    # on the same bf16 inputs: the forward kernel's output and last state,
    # and the backward kernel's six gradients. Decays drawn as the layer's
    # initialisation draws them, a quarter of the channels far faster (a
    # chunk forgets them) and some at none
    def kda_kernels(part):
        def check():
            rng = fresh_rng()
            from r2d2_tpu.ops import kda
            b, h, t, e, chunk = 1, 4, 1000, 128, 64
            bf = jnp.bfloat16

            def unit(*shape):
                x = rng.standard_normal(shape)
                return jnp.asarray(x / np.linalg.norm(x, axis=-1,
                                                      keepdims=True), bf)

            q, k = unit(b, h, t, e), unit(b, h, t, e)
            v = jnp.asarray(rng.standard_normal((b, h, t, e)), bf)
            rate = np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                      (b, h, t, e)))
            rate[..., : e // 4] *= 400.0
            rate[..., e // 4: e // 4 + 8] = 0.0
            g = jnp.asarray(-rng.uniform(1.0, 16.0, (1, h, 1, 1)) * rate,
                            jnp.float32)
            beta = jnp.asarray(rng.uniform(0.0, 1.0, (b, h, t)), jnp.float32)
            s0 = jnp.asarray(rng.standard_normal((b, h, e, e)) * 0.1,
                             jnp.float32)

            def close(got, want, what, atol=2e-3):
                got, want = (np.asarray(x, np.float32) for x in (got, want))
                assert np.isfinite(got).all(), what
                scale = max(float(np.abs(want).max()), 1e-30)
                np.testing.assert_allclose(got, want, rtol=0,
                                           atol=atol * scale, err_msg=what)

            o, s, states = kda.chunked_forward(q, k, v, g, beta, s0, chunk)
            o_ref, s_ref = jax.jit(kda.recurrent)(q, k, v, g, beta, s0)
            if part == "forward":
                close(o, o_ref, "o")
                close(s, s_ref, "state")
                return
            do = jnp.asarray(rng.standard_normal(o_ref.shape), jnp.float32)
            ds = jnp.asarray(rng.standard_normal(s_ref.shape), jnp.float32)
            want = jax.jit(lambda *x: jax.vjp(kda.recurrent, *x)[1](
                (do, ds)))(q, k, v, g, beta, s0)
            got = kda.chunked_backward(q, k, v, g, beta, states, do, ds,
                                       chunk)
            for x, y, what in zip(got, want,
                                  ("dq", "dk", "dv", "dg", "dbeta", "ds0")):
                close(x, y, what, 1e-2 if x.dtype == bf else 2e-3)
        return check
    for part in ("forward", "backward"):
        add(f"kda_scan_{part}", kda_kernels(part))

    # --- the kimi-linear-core cell's comparison, at its timed sizes, as
    # its runner makes it (benchmarks/runners/learner_8k_state.py): the
    # program's loss and Q against the plain reference (reference/
    # check.py), and the state row the window leaves, by part, on two
    # sequences of its full ring; and three controls that must fail it: the
    # scan with each chunk started from S = 0 (the chunks' carry dropped),
    # with the decays ignored (exp(g) = 1), and the core's products on
    # 8-bit (e4m3) operands. Each prints its errors against their limits.
    # Then the cell's step program, compiled for this chip as Learner
    # builds it, read for what XLA did to fit it
    def kimi_core():
        made = {}

        def prepare():
            if made:
                return made
            import tempfile
            from benchmarks import harness, traffic
            from benchmarks.reference import check as reference_check
            from benchmarks.reference import r2d2_kimi
            from r2d2_tpu.models.network import NetworkApply
            from r2d2_tpu.runtime.learner_loop import Learner
            bench = harness.load_benchmark()
            mix = harness.traffic_doc("learner-8k-state")
            cfg = harness.build_config(harness.program_overrides(
                harness.config_doc(bench, "kimi-linear-core"), mix),
                tempfile.mkdtemp(), 0)
            net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                               cfg.env.frame_height, cfg.env.frame_width)
            learner = Learner(cfg, net, 0, seed=0)
            try:
                traffic.fill_ring(learner, 6, mix["parameters"]["replay"], 0)
                batch = reference_check.sample_sequences(learner, 2, 1)
                params, target = jax.device_get(
                    (learner.train_state.params,
                     learner.train_state.target_params))
            finally:
                learner.stop_background()
            fields = {f.name: getattr(batch, f.name)
                      for f in dataclasses.fields(batch)
                      if getattr(batch, f.name) is not None}
            made.update(
                cfg=cfg, net=net, batch=batch, params=params, target=target,
                spec=dataclasses.replace(learner.spec, batch_size=2),
                reference=jax.device_get(r2d2_kimi.from_config(cfg)(
                    params, target, fields)))
            return made

        def compared(what):
            from benchmarks.reference import check as reference_check
            from benchmarks.runners import learner_8k_state as runner
            from r2d2_tpu.learner.train_step import make_loss_fn
            m = prepare()
            net = m["net"]
            loss, aux = jax.jit(make_loss_fn(net, m["spec"], m["cfg"].optim,
                                             True))(m["params"], m["target"],
                                                    m["batch"])
            program = jax.device_get({"loss": loss, **{
                k: aux[k] for k in ("priorities", "q_chosen", "abs_td")}})
            out = reference_check.compare(
                program, m["reference"], m["batch"].is_weights,
                reference_check.TOLERANCE["bfloat16"])
            row = runner.program_row(net, m["spec"], m["params"], m["batch"],
                                     True)
            out = runner.widen(out, runner.compare_state(
                row, m["reference"], dataclasses.asdict(m["cfg"].network.core),
                "bfloat16"))
            found = out["state"]
            errors = ", ".join(
                f"{k} {v:.4g} (limit {part['limits'][k]:.4g})"
                for part in (out, found) for k, v in part["errors"].items())
            print(f"  kimi_core {what}: {errors}; the state's errors per "
                  f"layer: {found['per_layer']}")
            return out["ok"], errors

        def with_scan(scan, what):
            from r2d2_tpu.ops import kda
            kept = kda.kda_scan
            kda.kda_scan = scan
            try:
                return compared(what)
            finally:
                kda.kda_scan = kept

        def sound():
            ok, errors = compared("comparison")
            assert ok, errors

        def carry_off():
            from r2d2_tpu.ops import kda
            kept = kda.kda_scan

            def scan(q, k, v, g, beta, s0, chunk):
                b, h, t, e = q.shape
                n = -(-t // chunk)

                def split(x):
                    x = jnp.pad(x, [(0, 0), (0, 0), (0, n * chunk - t)]
                                + [(0, 0)] * (x.ndim - 3))
                    x = x.reshape((b, h, n, chunk) + x.shape[3:])
                    return jnp.moveaxis(x, 2, 1).reshape(
                        (b * n, h, chunk) + x.shape[4:])

                o, s = kept(split(q), split(k), split(v), split(g),
                            split(beta), jnp.zeros((b * n,) + s0.shape[1:],
                                                   s0.dtype), chunk)
                o = jnp.moveaxis(o.reshape(b, n, h, chunk, -1), 1, 2)
                return (o.reshape(b, h, n * chunk, -1)[:, :, :t],
                        s.reshape((b, n) + s.shape[1:])[:, -1])

            ok, _ = with_scan(scan, "chunks' carry dropped")
            assert not ok, "the chunks' carry dropped passed the comparison"

        def decay_off():
            from r2d2_tpu.ops import kda
            kept = kda.kda_scan

            def scan(q, k, v, g, beta, s0, chunk):
                return kept(q, k, v, jnp.zeros_like(g), beta, s0, chunk)

            ok, _ = with_scan(scan, "decay ignored")
            assert not ok, "the decay ignored passed the comparison"

        def eight_bit():
            from r2d2_tpu.models.cores import experts, kda_mla_moe, mla_moe
            from r2d2_tpu.ops import kda
            f8 = jnp.float8_e4m3fn

            def coarse(x, dtype):
                return x.astype(f8).astype(dtype)

            kept = (experts.matmul, experts.grouped_matmul, kda.kda_scan)

            def matmul(spec, x, w, dtype, out_dtype=None):
                return kept[0](spec, coarse(x, dtype), coarse(w, dtype),
                               dtype, out_dtype)

            def grouped(rows, weights, sizes, dtype, out_dtype=None,
                        transposed=False):
                return kept[1](coarse(rows, dtype), coarse(weights, dtype),
                               sizes, dtype, out_dtype, transposed)

            def scan(q, k, v, *rest):
                return kept[2](coarse(q, q.dtype), coarse(k, k.dtype),
                               coarse(v, v.dtype), *rest)

            experts.matmul = mla_moe.matmul = kda_mla_moe.matmul = matmul
            experts.grouped_matmul = grouped
            kda.kda_scan = scan
            try:
                ok, _ = compared("8-bit operands")
            finally:
                experts.matmul = mla_moe.matmul = kept[0]
                kda_mla_moe.matmul = kept[0]
                experts.grouped_matmul = kept[1]
                kda.kda_scan = kept[2]
            assert not ok, "8-bit operands passed the comparison"

        def step_compile():
            import re
            from benchmarks import harness
            from r2d2_tpu.learner.train_step import (create_train_state,
                                                     make_multi_learner_step)
            from r2d2_tpu.models.network import NetworkApply
            from r2d2_tpu.replay.device_replay import replay_init
            from r2d2_tpu.replay.structs import ReplaySpec
            from r2d2_tpu.telemetry.learning import LearningDiag
            from r2d2_tpu.telemetry.replaydiag import ReplayDiag
            bench = harness.load_benchmark()
            mix = harness.traffic_doc("learner-8k-state")
            cfg = harness.build_config(harness.program_overrides(
                harness.config_doc(bench, "kimi-linear-core"), mix), "", 0)
            spec = ReplaySpec.from_config(cfg)
            net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                               cfg.env.frame_height, cfg.env.frame_width)
            train = jax.eval_shape(lambda: create_train_state(
                jax.random.PRNGKey(0), net, cfg.optim))
            ring = jax.eval_shape(lambda: replay_init(spec))
            step = make_multi_learner_step(
                net, spec, cfg.optim, cfg.network.use_double,
                cfg.runtime.resolved_steps_per_dispatch(),
                diag=LearningDiag.from_config(cfg),
                rdiag=ReplayDiag.from_config(cfg))
            compiled = step.lower(train, ring).compile()
            memory = compiled.memory_analysis()
            text = compiled.as_text()
            remat = re.findall(r"%(\S+\.remat\d*) = ", text)
            laid = re.findall(r"f32\[8,\d+,\d+\]\{1,2,0", text)
            t, m = cfg.sequence.seq_len, cfg.network.core.memory_len
            heads = cfg.network.core.num_attention_heads
            whole = [x for x in re.findall(r"\[([\d,]+)\]", text)
                     if {str(t), str(t + m), str(heads)}
                     <= set(x.split(","))]
            gib = 2.0 ** 30
            print(f"  kimi_core step: temp {memory.temp_size_in_bytes / gib:.3f}"
                  f" GiB, arguments {memory.argument_size_in_bytes / gib:.3f}"
                  f" GiB, the step's peak (arguments + temp) "
                  f"{(memory.argument_size_in_bytes + memory.temp_size_in_bytes) / gib:.3f}"
                  f" GiB; {len(remat)} remat instructions "
                  f"{sorted(set(re.sub(r'[.]\d+', '', r) for r in remat))}; "
                  f"{len(laid)} float32 expert arrays laid out {{1,2,0}}; "
                  f"{len(whole)} arrays of queries x keys x heads")
            assert not laid, "a float32 expert array laid out {1,2,0}"
            assert not whole, "an array of queries x keys x heads"

        return {"kimi_core_sound": sound,
                "kimi_core_carry_off_fails": carry_off,
                "kimi_core_decay_off_fails": decay_off,
                "kimi_core_8bit_fails": eight_bit,
                "kimi_core_step_compile": step_compile}
    for name, fn in kimi_core().items():
        add(name, fn)

    # --- the target held in the dtype its forward reads
    # (learner/target.py) at the widths of the benchmark's configurations
    # with experts: through one program (float32 in), the float32 tree and
    # the same tree with the leaves the plan narrows rounded to bf16 first
    # give the same Q, state and routers' counters bit for bit; the
    # routers' kernels, which the plan keeps, rounded as well do not. The
    # reference check cannot see a leaf narrowed by mistake (it is handed
    # the program's own target); this can
    def target_plan(config_name, batch=16):
        def check():
            rng = fresh_rng()
            from benchmarks import harness
            from r2d2_tpu.envs.factory import create_env
            from r2d2_tpu.learner.target import target_plan as plan_of
            from r2d2_tpu.models.network import NetworkApply
            config = harness.config_doc(harness.load_benchmark(), config_name)
            cfg = harness.build_config(
                harness.program_overrides(config, {}), "", 0)
            env = create_env(cfg.env, seed=0)
            net = NetworkApply(env.action_space.n, cfg.network,
                               cfg.env.frame_stack, cfg.env.frame_height,
                               cfg.env.frame_width)
            env.close()
            params = net.init(jax.random.PRNGKey(0))
            plan = plan_of(net, params)
            assert plan is not None, "nothing narrowed"
            h, w, s = net.obs_hw
            window = (cfg.sequence.burn_in_steps + cfg.sequence.learning_steps
                      + cfg.sequence.forward_steps)
            obs = jnp.asarray(rng.random((batch, window, h, w, s)),
                              net.module.compute_dtype)
            la = jax.nn.one_hot(rng.integers(0, net.action_dim,
                                             (batch, window)),
                                net.action_dim)
            hidden = net.init_state(batch)
            forward = jax.jit(net.apply_learner)
            want = jax.tree_util.tree_leaves(jax.device_get(
                forward(params, obs, la, hidden)))

            def same(p):
                got = jax.tree_util.tree_leaves(jax.device_get(
                    forward(p, obs, la, hidden)))
                return all(np.array_equal(x, y) for x, y in zip(got, want))

            assert same(jax.tree_util.tree_map(
                lambda d, p: p.astype(d).astype(p.dtype), plan, params)), \
                "the leaves the plan narrows, rounded first, moved the forward"
            assert not same(jax.tree_util.tree_map_with_path(
                lambda path, p: (p.astype(jnp.bfloat16).astype(p.dtype)
                                 if getattr(path[-1], "key", None) == "gate"
                                 else p), params)), \
                "rounding the routers' kernels left the forward as it was"
        return check
    add("target_plan_moonlight_core", target_plan("moonlight-core"))
    add("target_plan_lfm2_core", target_plan("lfm2-core"))

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
