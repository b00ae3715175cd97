"""Operations of the ``mla_moe`` memory core (``r2d2_tpu/models/cores/
mla_moe.py``) as functions of the configuration, for the readers that hold
the ``moonlight-core`` cells to the chip's peak. Beside ``costs.py``, whose
table of peaks and whose count of the torso they use.

Counted: the matrix products the mathematics needs, at 2 FLOPs a
multiply-add. Not counted: norms, the rotation, the softmax, the sort and
gather of the routed pairs, Adam, and anything computed twice (the layers are
rematerialised in the backward pass; that is not model work).

In ``step_flops`` the held experts are counted at their expected share: a
position's ``num_experts_per_tok`` choices fall on one of the
``experts_held`` of ``n_routed_experts`` with probability held / routed each,
so a position brings ``top_k * held / routed`` pairs (0.75 at 6 x 8 / 64).
The core's router, which reads its input less its mean over positions, gives
that count within a few per cent on a seeded batch of 8,000 positions
(PERF.md, Findings, PR 27 has the chip's counts). The held experts are a
ninth of the step's count, so a few per cent of them are a few tenths of a
per cent of ``mfu_bf16``. ``experts_flops`` counts them from the pairs that
did fall on them (the program's ``moe`` counter ``pairs_held``, which
``runners/learner.py`` hands the readers), for ``k_experts_roofline``.
"""

from typing import Dict

from benchmarks import costs


def core_macs_per_position(core, in_dim: int, window: int) -> Dict[str, float]:
    """Multiply-adds of one forward pass of the whole stack for one window
    position, by part. ``window`` is the window's length T: a position's
    attention sees the ``memory_len`` stored slots and, on average,
    (T + 1) / 2 of the window; the expansion of the stored slots' keys and
    values (once a sequence) is spread over its T positions."""
    d, heads = core.hidden_size, core.num_attention_heads
    dn, dr, dv, dc = (core.qk_nope_head_dim, core.qk_rope_head_dim,
                      core.v_head_dim, core.kv_lora_rank)
    layers, dense = core.num_hidden_layers, core.first_k_dense_replace
    keys_seen = core.memory_len + (window + 1) / 2.0
    keys_expanded = (core.memory_len + window) / window
    attention = (d * heads * (dn + dr) + d * (dc + dr)
                 + keys_expanded * dc * heads * (dn + dv)
                 + keys_seen * heads * (dn + dr + dv)
                 + heads * dv * d)
    expert = 3 * d * core.moe_intermediate_size
    pairs = (core.num_experts_per_tok * core.experts_held
             / core.n_routed_experts)
    return {
        "input_proj": float(in_dim * d),
        "mla_attn": layers * float(attention),
        "dense_mlp": dense * 3.0 * d * core.intermediate_size,
        "moe_router": (layers - dense) * float(d * core.n_routed_experts),
        "moe_shared": (layers - dense) * float(expert * core.n_shared_experts),
        "moe_experts": (layers - dense) * pairs * expert,
    }


def _torso_and_head_macs(cfg, action_dim: int):
    """(multiply-adds of the torso and the head for one frame, the first
    convolution's share), the torso as ``costs.py`` counts it and the
    dueling head reading the core's ``hidden_size``."""
    net, env = cfg.network, cfg.env
    # with hidden_dim 0 what is left is the convolutions and the dense layer
    torso, first_conv = costs._macs_per_frame(
        net.conv_layers, env.frame_height, env.frame_width, env.frame_stack,
        net.cnn_out_dim, 0, action_dim, net.use_dueling)
    streams = 2 if net.use_dueling else 1
    head = (streams * net.core.hidden_size * net.hidden_dim
            + net.hidden_dim * (action_dim + (1 if net.use_dueling else 0)))
    return torso + head, first_conv


def passes(cfg) -> float:
    """Forward passes' worth of work a train step does on a position:
    forward and backward of the online net (a backward is two forwards),
    and the target net's forward under double-Q."""
    return 3.0 + (1.0 if cfg.network.use_double else 0.0)


def step_flops(cfg, action_dim: int) -> float:
    """Model FLOPs of one train step: torso, core and head over
    batch x window positions. The first convolution's input gradient is
    never computed, so it counts one pass fewer (as in ``costs.py``)."""
    positions = cfg.replay.batch_size * cfg.sequence.seq_len
    outer, first_conv = _torso_and_head_macs(cfg, action_dim)
    core = sum(core_macs_per_position(
        cfg.network.core, cfg.network.cnn_out_dim + action_dim,
        cfg.sequence.seq_len).values())
    return 2.0 * positions * ((outer + core) * passes(cfg) - first_conv)


def experts_flops(cfg, pairs_held: float) -> float:
    """Model FLOPs the held experts' grouped products owe for ``pairs_held``
    (position, expert) pairs that the online net's routers put on them,
    summed over the expert layers: a pair is one row through the three
    products of its expert's SwiGLU (3 x hidden x width multiply-adds a
    forward pass), in every pass a step makes (``passes``; the target net's
    routers are taken to put as many pairs there as the online net's). The
    rows a chunked walk pads its groups with are no model work, and neither
    is the forward pass recomputed inside the backward: a walk that pads
    more, or recomputes more, takes longer for the same count."""
    core = cfg.network.core
    return (2.0 * 3 * core.hidden_size * core.moe_intermediate_size
            * passes(cfg) * pairs_held)
