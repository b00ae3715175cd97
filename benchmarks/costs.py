"""The yardstick's arithmetic: the table of peaks, the model's operations per
train step and the bytes each kernel's call needs, all as functions of shapes.

Copied from ``r2d2_tpu/telemetry/costmodel.py`` (``PEAK_SPECS``,
``model_flops_per_step``, here ``step_flops``), whose counts are reconciled
with XLA's ``cost_analysis`` in ``tests/test_costmodel.py``. The copy is
deliberate: a later PR may change the program's table, not the one it is
measured with.

A configuration names the module that counts its model work under ``costs``
(``harness.costs_of``); this one counts the LSTM network, ``costs_mla_moe.py``
the network with the ``mla_moe`` core. Each gives ``step_flops(cfg,
action_dim)``, which ``mfu_bf16`` reads.
"""

from typing import Dict, Sequence, Tuple

# Per chip, keyed by ``jax.devices()[0].device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s of HBM, 16 GB).
# A device that is not here is an error, not a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak figures for device_kind {device_kind!r}: add its "
            "published per-chip peaks, with their source, to "
            f"benchmarks/costs.py PEAKS (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def _macs_per_frame(conv_layers: Sequence[Tuple[int, int, int]], height: int,
                    width: int, stack: int, cnn_out_dim: int, hidden_dim: int,
                    action_dim: int, dueling: bool) -> Tuple[float, float]:
    """(all matmul MACs for one frame's forward, the first conv's share)."""
    h, w, c = height, width, stack
    conv = []
    for features, kernel, stride in conv_layers:
        h = (h - kernel) // stride + 1
        w = (w - kernel) // stride + 1
        conv.append(h * w * features * kernel * kernel * c)
        c = features
    fc = h * w * c * cnn_out_dim
    lstm = 4 * hidden_dim * (cnn_out_dim + action_dim + hidden_dim)
    head = hidden_dim * hidden_dim + hidden_dim * action_dim
    if dueling:
        head += hidden_dim * hidden_dim + hidden_dim
    return float(sum(conv) + fc + lstm + head), float(conv[0] if conv else 0)


def step_flops(cfg, action_dim: int) -> float:
    """Model FLOPs of one train step on one chip's batch: forward and
    backward (2x forward) of the online net, plus the target net's forward
    under double-Q, over batch x window frames at 2 FLOPs a MAC. The first
    conv's input gradient is never computed (observations need none), so it
    counts one pass fewer. Elementwise work, the decode and Adam are not
    counted, and nothing recomputed is."""
    net, env = cfg.network, cfg.env
    macs, first_conv = _macs_per_frame(
        net.conv_layers, env.frame_height, env.frame_width, env.frame_stack,
        net.cnn_out_dim, net.hidden_dim, action_dim, net.use_dueling)
    passes = 3.0 + (1.0 if net.use_double else 0.0)
    frames = cfg.replay.batch_size * cfg.sequence.seq_len
    return 2.0 * frames * (macs * passes - first_conv)


def window_frames(cfg) -> int:
    """Stored frames one sampled sequence spans: the window plus the
    stacking margin."""
    return cfg.sequence.seq_len + cfg.env.frame_stack - 1


def gather_bytes_needed(cfg) -> float:
    """Bytes one call of the window gather has to move: each sampled
    sequence's uint8 frames read from the ring once and written to the batch
    once, at the true frame size. Storage padding (84x84 held as 96x128 for
    the exact gather) is moved on top of this and so shows as a lower share
    of the roofline, which is what it costs."""
    frame = cfg.env.frame_height * cfg.env.frame_width
    return 2.0 * cfg.replay.batch_size * window_frames(cfg) * frame


def decode_bytes_needed(cfg, act_bytes: int) -> float:
    """Bytes one decode call has to move: the batch's uint8 frames read
    once, and the stacked, normalised observations (window x stack planes a
    sequence) written once in the compute type."""
    frame = cfg.env.frame_height * cfg.env.frame_width
    b = cfg.replay.batch_size
    read = b * window_frames(cfg) * frame
    write = b * cfg.sequence.seq_len * cfg.env.frame_stack * frame * act_bytes
    return float(read + write)
