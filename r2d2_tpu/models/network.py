"""Recurrent dueling/double DQN in Flax — the R2D2 model, TPU-first.

Capability parity with the reference PyTorch ``Network``
(/root/reference/model.py:8-157): Nature-DQN conv torso, LSTM over
[cnn latent ⊕ one-hot last action], dueling value/advantage heads with
mean-advantage baseline, and the four inference modes (single ``step``,
grad-enabled sequence Q, no-grad target sequence Q at t+n, hidden reset).

TPU-native re-design rather than translation:

* **One unroll, not three.** The reference runs three LSTM passes per train
  step: online ``caculate_q_`` for double-DQN action selection, target
  ``caculate_q_``, and grad-enabled online ``caculate_q``
  (/root/reference/worker.py:335-344). Because an LSTM output at t depends
  only on inputs <= t, the online pass over the full window subsumes both
  online passes: Q(s_t) and the action-selection Q(s_{t+n}) are *gathers from
  the same unrolled outputs* (see ops/indexing.py). Only the target net needs
  a second unroll — 2 sequential passes instead of 3.
* **Static shapes.** No pack/pad (/root/reference/model.py:103-108): every
  sequence unrolls the full fixed window under ``lax.scan``; ragged semantics
  live in gather indices + masks computed in ops/indexing.py.
* **NHWC convs + bf16 policy.** Channels-last is the TPU-friendly conv
  layout; ``compute_dtype=bfloat16`` replaces torch.cuda.amp
  (/root/reference/config.py:35) with f32 params and f32 Q outputs.
* **Sharding-ready.** Kernel params carry logical sharding annotations
  (``nn.with_partitioning``-free: we annotate at the mesh layer instead so a
  1-device run pays nothing) — model parallelism is a mesh-axis change.
"""

from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.config import NetworkConfig

# Hidden-state packing convention matches the reference actor protocol:
# packed[0] = h, packed[1] = c (torch.cat(hidden_state) at
# /root/reference/model.py:84). Flax LSTMCell carries (c, h).


def pack_hidden(carry: Tuple[jnp.ndarray, jnp.ndarray]) -> jnp.ndarray:
    c, h = carry
    return jnp.stack([h, c], axis=-2)  # (..., 2, hidden)


def unpack_hidden(packed: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = packed[..., 0, :]
    c = packed[..., 1, :]
    return (c, h)


def initial_hidden(batch_size: int, hidden_dim: int, dtype=jnp.float32) -> jnp.ndarray:
    """Zero packed hidden state (ref model.py:34,86-87)."""
    return jnp.zeros((batch_size, 2, hidden_dim), dtype=dtype)


class ConvTorso(nn.Module):
    """Nature-DQN feature extractor (ref model.py:22-31), NHWC.

    Input: (B, H, W, stack) normalized f32/bf16. Output: (B, cnn_out_dim).
    """

    cnn_out_dim: int
    conv_layers: Sequence[Tuple[int, int, int]]
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for features, kernel, stride in self.conv_layers:
            # VALID padding matches torch Conv2d's default zero-pad=0.
            x = nn.Conv(
                features,
                (kernel, kernel),
                strides=(stride, stride),
                padding="VALID",
                dtype=self.dtype,
            )(x)
            x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(self.cnn_out_dim, dtype=self.dtype)(x)
        return x


class DuelingHead(nn.Module):
    """Dueling Q decomposition q = v + a - mean(a) (ref model.py:36-46,59-63)."""

    action_dim: int
    hidden_dim: int
    use_dueling: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        adv = nn.Dense(self.hidden_dim, dtype=self.dtype, name="adv_hidden")(h)
        adv = nn.relu(adv)
        adv = nn.Dense(self.action_dim, dtype=self.dtype, name="adv_out")(adv)
        if not self.use_dueling:
            return adv.astype(jnp.float32)
        val = nn.Dense(self.hidden_dim, dtype=self.dtype, name="val_hidden")(h)
        val = nn.relu(val)
        val = nn.Dense(1, dtype=self.dtype, name="val_out")(val)
        q = val + adv - jnp.mean(adv, axis=-1, keepdims=True)
        return q.astype(jnp.float32)


def _block_orthogonal_init(num_blocks: int):
    """Per-gate orthogonal recurrent init, concatenated — the same
    distribution as flax's per-gate ``recurrent_kernel_init=orthogonal()``
    (one semi-orthogonal (H, num_blocks*H) draw would correlate gates)."""
    base = nn.initializers.orthogonal()

    def init(key, shape, dtype=jnp.float32):
        rows, cols = shape
        block = cols // num_blocks
        keys = jax.random.split(key, num_blocks)
        return jnp.concatenate(
            [base(k, (rows, block), dtype) for k in keys], axis=1)

    return init


def lstm_cell_step(xp, c, h, w_rec, bias):
    """One LSTM step given the precomputed input projection ``xp`` =
    x_t @ Wi. THE cell math (gate order i,f,g,o; sigmoid/sigmoid/tanh/
    sigmoid) — shared by the in-chip scan (HoistedLSTM) and the
    sequence-parallel pipelined scan (parallel/sequence_parallel.py), so
    the two cannot diverge."""
    gates = xp + h @ w_rec + bias
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    new_c = nn.sigmoid(f) * c + nn.sigmoid(i) * jnp.tanh(g)
    new_h = nn.sigmoid(o) * jnp.tanh(new_c)
    return new_c, new_h


class HoistedLSTM(nn.Module):
    """LSTM over a (B, T, D) sequence with the input projection hoisted out
    of the time scan.

    One LSTM step is ``gates = x_t @ Wi + h @ Wh + b``. The ``x @ Wi`` term
    has no serial dependency, so it is computed for the WHOLE window as one
    (B*T, D) x (D, 4H) MXU matmul before the scan; the scan body keeps only
    the (B, H) x (H, 4H) recurrent matmul — shrinking the work on the
    55-step serial dependency chain ~3x at the reference scale (D=1042,
    H=512). Identical math to ``nn.OptimizedLSTMCell`` (gate order i,f,g,o,
    sigmoid/sigmoid/tanh/sigmoid, c'=f*c+i*g, h'=o*tanh(c')), verified
    param-for-param in tests/test_network.py. Replaces the reference's
    cuDNN ``nn.LSTM`` (/root/reference/model.py:33)."""

    features: int
    dtype: jnp.dtype = jnp.float32
    # lax.scan unroll factor: >1 trades compile time/code size for fewer
    # loop-iteration boundaries on the serial chain (NetworkConfig.scan_unroll)
    unroll: int = 1

    @nn.compact
    def __call__(self, carry, xs):
        # carry: (c, h) each (B, H); xs: (B, T, D)
        hidden = self.features
        x_proj = nn.Dense(4 * hidden, use_bias=False, dtype=self.dtype,
                          name="input_proj")(xs)              # (B, T, 4H)
        w_rec = self.param("recurrent_kernel", _block_orthogonal_init(4),
                           (hidden, 4 * hidden))
        bias = self.param("bias", nn.initializers.zeros, (4 * hidden,))
        w_rec = w_rec.astype(self.dtype)
        bias = bias.astype(self.dtype)

        def step(carry, xp):                                  # xp: (B, 4H)
            new_c, new_h = lstm_cell_step(xp, carry[0], carry[1], w_rec, bias)
            return (new_c, new_h), new_h

        carry, outputs = jax.lax.scan(step, carry, x_proj.swapaxes(0, 1),
                                      unroll=self.unroll)
        return carry, outputs.swapaxes(0, 1)                  # (B, T, H)


def _torso_batch(obs_seq, dtype):
    """The torso's batch of frames and the way back from it: (frames
    (N, H, W, K), rows (N, D) -> (B, T, D)). A (B, T, H, W, K) array
    flattens sequence by sequence; ``LaneFrames`` (the TPU decode,
    ops/pallas_kernels.py) are flattened already, in the order that puts
    the frame index in the first convolution's lanes, and know the way
    back. The torso treats frames one by one, so the order changes no
    value."""
    from r2d2_tpu.ops.pallas_kernels import LaneFrames
    if isinstance(obs_seq, LaneFrames):
        return obs_seq.frames.astype(dtype), obs_seq.sequence
    batch, seq = obs_seq.shape[0], obs_seq.shape[1]
    return (obs_seq.astype(dtype).reshape(batch * seq, *obs_seq.shape[2:]),
            lambda rows: rows.reshape(batch, seq, rows.shape[-1]))


class R2D2Network(nn.Module):
    """The full recurrent Q-network.

    ``__call__`` is the single entry point: unroll T steps from a packed
    hidden state, returning Q for every step plus the final packed hidden.
    T=1 is the actor's ``step``; T=seq_len is the learner's sequence pass.
    """

    action_dim: int
    config: NetworkConfig

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.config.bf16 else jnp.float32

    @nn.compact
    def __call__(
        self,
        obs_seq: jnp.ndarray,       # (B, T, H, W, stack) normalized [0,1],
                                    # or LaneFrames of that logical shape
        last_action_seq: jnp.ndarray,  # (B, T, action_dim) one-hot f32
        hidden: jnp.ndarray,        # (B, 2, core.state_half) packed
        window_stats: bool = False,  # the learner's call (models/cores/)
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.config
        dtype = self.compute_dtype
        batch, seq = obs_seq.shape[0], obs_seq.shape[1]

        # Torso over the flattened (B*T) frame batch — one big conv batch is
        # the MXU-friendly shape (vs per-step convs inside the scan).
        # The module names ("torso"/"lstm"/"head") double as the
        # component annotation contract (ISSUE 9): flax emits each as a
        # jax.named_scope, so every HLO op carries the component in its
        # op_name metadata and xprof traces attribute device time per
        # component (telemetry/traceparse.py keys on these exact tokens).
        flat, to_sequence = _torso_batch(obs_seq, dtype)
        latent = ConvTorso(cfg.cnn_out_dim, cfg.conv_layers, dtype,
                           name="torso")(flat)
        latent = to_sequence(latent)

        rnn_in = jnp.concatenate(
            [latent, last_action_seq.astype(dtype)], axis=-1
        )

        # The memory core (models/cores/): the LSTM, or whichever
        # ``cfg.core.kind`` names. It builds its modules here, under its
        # own scope name.
        from r2d2_tpu.models.cores import make_core
        core = make_core(cfg, dtype)
        outputs, new_hidden = core.unroll(rnn_in, hidden, window_stats)

        q = DuelingHead(
            self.action_dim, cfg.hidden_dim, cfg.use_dueling, dtype, name="head"
        )(outputs.reshape(batch * seq, core.out_dim))
        q = q.reshape(batch, seq, self.action_dim)
        return q, new_hidden


# ---------------------------------------------------------------------------
# Quantized inference plane (ISSUE 14): per-channel symmetric int8 / bf16
# weight twins for the ACTING forward. The acting forward is
# weight-streaming-bound at acting batch sizes (tiny per-request FLOPs
# against full param-bytes HBM traffic — the costmodel tables; Podracer,
# arXiv 2104.06272), so shrinking weight bytes is the direct multiplier
# on env-steps/s and serving requests/s. Quantization happens ONCE at
# weight publish (runtime/weights.py ships the twin; no hot-path
# requantization); the forward dequantizes per-channel into the compute
# matmul. The learner never sees any of this — training stays f32/bf16.
# ---------------------------------------------------------------------------

INFERENCE_DTYPES = ("f32", "bf16", "int8")


def quant_compute_dtype():
    """Compute dtype of the quantized forward's matmuls: bf16 on TPU
    (the MXU-native acting dtype — the int8 weights dequantize into it),
    f32 elsewhere (bf16 is emulated and slower on CPU hosts, the
    _force_f32 reasoning; int8 storage still cuts publish bytes there).
    Resolved per-process at trace time, like the sibling tri-states."""
    import jax
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def quantize_leaf_int8(w: jnp.ndarray) -> dict:
    """Per-channel symmetric int8 quantization of one kernel: the scale
    is max|w| over all axes but the LAST (the output-channel axis of
    conv/dense/LSTM kernels) / 127, so each output channel keeps its own
    dynamic range — the standard per-channel weight-only scheme. The
    round-trip error is bounded by scale/2 per element (tested)."""
    w = jnp.asarray(w, jnp.float32)
    axes = tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    scale = jnp.maximum(scale, jnp.float32(1e-12))   # all-zero channels
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def _is_quant_leaf(leaf) -> bool:
    return isinstance(leaf, dict) and "q" in leaf and "scale" in leaf


def dequantize_leaf(leaf, dtype):
    """Inverse of quantize_leaf_int8 (or a plain cast for bf16-twin /
    unquantized leaves): int8 -> f32 per-channel rescale -> compute
    dtype. Inside a jitted forward XLA fuses this into the consumer
    matmul's operand read, so HBM weight traffic stays int8."""
    if _is_quant_leaf(leaf):
        return (leaf["q"].astype(jnp.float32) * leaf["scale"]).astype(dtype)
    return jnp.asarray(leaf).astype(dtype)


def dequantize_tree(tree, dtype):
    return jax.tree_util.tree_map(lambda l: dequantize_leaf(l, dtype),
                                  tree, is_leaf=_is_quant_leaf)


def quantize_params(params, inference_dtype: str):
    """The publish-time weight twin for one inference dtype:

      * ``"f32"``  — ``params`` unchanged (identity; the kill switch);
      * ``"bf16"`` — every float leaf cast to bf16 (2x weight bytes);
      * ``"int8"`` — every kernel (float ndim >= 2: conv kernels, dense
        kernels, the LSTM input projection and recurrent kernel) becomes
        a per-channel {"q": int8, "scale": f32} pair (~4x kernel bytes);
         1-D leaves (biases) stay f32 — they are noise against the
        kernels and the LSTM cell math wants them full-precision.
    """
    if inference_dtype == "f32":
        return params
    if inference_dtype == "bf16":
        return jax.tree_util.tree_map(
            lambda w: (jnp.asarray(w).astype(jnp.bfloat16)
                       if jnp.issubdtype(jnp.asarray(w).dtype, jnp.floating)
                       else jnp.asarray(w)), params)
    if inference_dtype != "int8":
        raise ValueError(
            f"inference_dtype must be one of {INFERENCE_DTYPES}, got "
            f"{inference_dtype!r}")

    def one(w):
        w = jnp.asarray(w)
        if w.ndim >= 2 and jnp.issubdtype(w.dtype, jnp.floating):
            return quantize_leaf_int8(w)
        return w.astype(jnp.float32)

    return jax.tree_util.tree_map(one, params)


def is_quant_bundle(tree) -> bool:
    """True for the published {"f32", "quant", "stamp"} bundle (vs a raw
    param tree, whose top level is flax's {"params": ...})."""
    return isinstance(tree, dict) and "quant" in tree and "f32" in tree


def make_inference_bundle(net: "NetworkApply", params, stamp: int = 0):
    """The tree the weight service publishes when
    ``net.config.inference_dtype != "f32"``: the f32 params (the probe's
    reference twin), the quantized twin (the hot path), and the
    publication stamp the twin was built at — so staleness between the
    two halves is impossible by construction and testable (the
    publish-time-twin stamp rides every adoption). For "f32" the raw
    params ARE the published tree (byte-identical plumbing)."""
    mode = net.config.inference_dtype
    if mode == "f32":
        return params
    return {"f32": params,
            "quant": quantize_params(params, mode),
            "stamp": jnp.asarray(stamp, jnp.int32)}


def f32_reference_module(net: "NetworkApply") -> "R2D2Network":
    """The accuracy probe's reference twin: TRUE f32 whatever the
    learner's compute policy — the guard measures quantization against
    the unquantized policy, not against bf16's own rounding. ONE
    definition shared by the host/server forward (make_forward_fn) and
    the anakin segment probe, so the two probes can never measure
    against different references."""
    import dataclasses
    return R2D2Network(action_dim=net.action_dim,
                       config=dataclasses.replace(net.config, bf16=False))


def quantized_inference_apply(net: "NetworkApply", qparams,
                              obs_seq: jnp.ndarray,
                              last_action_seq: jnp.ndarray,
                              hidden: jnp.ndarray,
                              compute_dtype=None
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The quantized twin of ``R2D2Network.__call__``: same signature,
    same module components (ConvTorso / DuelingHead via raw .apply, the
    shared ``lstm_cell_step``), but the
    weights come dequantized per-channel from the published twin and the
    LSTM CARRY STAYS f32: the recurrent state crosses acting steps
    thousands of times, so carrying it (and the cell math) in f32 keeps
    quantization error per-step instead of compounding — the recurrent
    matmul at acting batch is latency-bound anyway (PERF.md), so the
    f32 promotion costs nothing where this forward runs. Torso, the
    hoisted input projection, and the head run in ``compute_dtype``
    (bf16 on TPU, quant_compute_dtype); Q returns f32 like every other
    forward."""
    cfg = net.config
    dtype = compute_dtype if compute_dtype is not None \
        else quant_compute_dtype()
    qp = qparams["params"]
    batch, seq = obs_seq.shape[0], obs_seq.shape[1]

    flat = obs_seq.astype(dtype).reshape(batch * seq, *obs_seq.shape[2:])
    torso = ConvTorso(cfg.cnn_out_dim, cfg.conv_layers, dtype)
    # explicit component scopes: raw .apply calls carry no flax module
    # names, and the trace→component mapping (telemetry/traceparse.py)
    # keys on these exact tokens
    with jax.named_scope("torso"):
        latent = torso.apply({"params": dequantize_tree(qp["torso"], dtype)},
                             flat)
    rnn_in = jnp.concatenate(
        [latent.reshape(batch, seq, cfg.cnn_out_dim),
         last_action_seq.astype(dtype)], axis=-1)

    lp = qp["lstm"]
    wi = dequantize_leaf(lp["input_proj"]["kernel"], dtype)
    w_rec = dequantize_leaf(lp["recurrent_kernel"], jnp.float32)
    bias = dequantize_leaf(lp["bias"], jnp.float32)
    with jax.named_scope("lstm"):
        # hoisted input projection in the compute dtype; the serial cell
        # chain in f32 (carry + gates — see docstring)
        xp = (rnn_in @ wi).astype(jnp.float32).swapaxes(0, 1)  # (T, B, 4H)
        carry = unpack_hidden(hidden.astype(jnp.float32))

        def step(c, xpt):
            new_c, new_h = lstm_cell_step(xpt, c[0], c[1], w_rec, bias)
            return (new_c, new_h), new_h

        carry, outputs = jax.lax.scan(step, carry, xp,
                                      unroll=cfg.scan_unroll)

    head = DuelingHead(net.action_dim, cfg.hidden_dim, cfg.use_dueling,
                       dtype)
    with jax.named_scope("head"):
        q = head.apply(
            {"params": dequantize_tree(qp["head"], dtype)},
            outputs.swapaxes(0, 1).reshape(batch * seq,
                                           cfg.hidden_dim).astype(dtype))
    return (q.reshape(batch, seq, net.action_dim),
            pack_hidden(carry).astype(jnp.float32))


def param_tree_bytes(tree) -> int:
    """Total bytes of a (possibly quantized) param tree — the analytic
    weight-streaming denominator the costmodel's quant rows and the
    quant A/B artifact quote (int8 twin vs f32: the >= 3x cut)."""
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        # works for jax/np arrays AND ShapeDtypeStruct avals
        total += int(np.prod(leaf.shape) if leaf.shape else 1) * \
            np.dtype(leaf.dtype).itemsize
    return int(total)


class NetworkApply:
    """Thin convenience binding of jitted apply functions to a network spec.

    Pure-functional: holds no parameters, only shapes/config. Used by the
    actor policy (CPU) and the learner (TPU); both call the same module so
    weight exchange is a raw pytree copy, never a format conversion (the
    reference ships state_dicts through Ray's object store instead,
    /root/reference/worker.py:286-290).
    """

    def __init__(self, action_dim: int, config: NetworkConfig,
                 frame_stack: int, frame_height: int, frame_width: int):
        # Resolve the bf16 tri-state here — ONE place — so the module and
        # every consumer of .config see a concrete bool ("auto" = bf16 iff
        # the default backend is TPU, the measured winner there: +28% with
        # the native-dtype decode, PERF.md; CPU backends keep f32, where
        # bf16 is emulated and slower).
        from r2d2_tpu.ops.pallas_kernels import resolve_pallas_setting
        import dataclasses
        config = dataclasses.replace(
            config, bf16=resolve_pallas_setting(config.bf16, "network.bf16"))
        self.action_dim = action_dim
        self.config = config
        self.obs_hw = (frame_height, frame_width, frame_stack)
        # Validate the conv pyramid against the frame size up front — a
        # zero/negative spatial output otherwise surfaces as an opaque
        # ZeroDivisionError inside flax's variance-scaling initializer.
        h, w = frame_height, frame_width
        for i, (_, kernel, stride) in enumerate(config.conv_layers):
            h = (h - kernel) // stride + 1
            w = (w - kernel) // stride + 1
            if h < 1 or w < 1:
                raise ValueError(
                    f"conv layer {i} (kernel {kernel}, stride {stride}) "
                    f"shrinks the {frame_height}x{frame_width} frame to "
                    f"{h}x{w}; use smaller network.conv_layers for this "
                    "frame size")
        if config.core.kind != "lstm" and config.inference_dtype != "f32":
            raise ValueError(
                "network.inference_dtype other than 'f32' quantizes the "
                "LSTM's acting forward (quantized_inference_apply); "
                f"network.core.kind={config.core.kind!r} has no such twin")
        self.module = R2D2Network(action_dim=action_dim, config=config)
        from r2d2_tpu.models.cores import make_core
        self.core = make_core(config, self.module.compute_dtype)

    @property
    def state_half(self) -> int:
        """Half the width of the packed recurrent-state row (B, 2, .)."""
        return self.core.state_half

    def init_state(self, batch_size: int) -> jnp.ndarray:
        """The packed state an episode starts from."""
        return self.core.init_state(batch_size)

    def init(self, key: jax.Array):
        h, w, s = self.obs_hw
        obs = jnp.zeros((1, 1, h, w, s), jnp.float32)
        la = jnp.zeros((1, 1, self.action_dim), jnp.float32)
        args = (key, obs, la, self.init_state(1))
        if self.config.core.kind == "lstm":
            return self.module.init(*args)
        # a core of half a billion parameters is drawn on the device in one
        # program, not leaf by leaf; only "params" is kept (the core sows
        # counters while it runs)
        return {"params": jax.jit(self.module.init)(*args)["params"]}

    def apply(self, params, obs_seq, last_action_seq, hidden):
        return self.module.apply(params, obs_seq, last_action_seq, hidden)

    def apply_learner(self, params, obs_seq, last_action_seq, hidden):
        """The learner's forward pass over a batch of windows: ``apply``
        with the core's ``window_stats`` on, and the counters the core sowed
        into the ``moe`` collection while it ran (the routing of a core with
        experts: models/cores/experts.py; {} for a core that sows none),
        with, for a core that sows counters of its own (its loss among
        them), those under ``"core"`` (models/cores/__init__.py)."""
        from r2d2_tpu.models.cores.experts import moe_counters
        (q, new_hidden), sown = self.module.apply(
            params, obs_seq, last_action_seq, hidden, True,
            mutable=["moe", "core"])
        counters, own = dict(moe_counters(sown)), moe_counters(sown, "core")
        if own:
            counters["core"] = own
        return q, new_hidden, counters


def init_network(
    key: jax.Array,
    action_dim: int,
    config: NetworkConfig,
    frame_stack: int = 4,
    frame_height: int = 84,
    frame_width: int = 84,
):
    """Initialize (apply_spec, params)."""
    spec = NetworkApply(action_dim, config, frame_stack, frame_height, frame_width)
    return spec, spec.init(key)
