"""Operations of the ``conv_attn_moe`` memory core (``r2d2_tpu/models/cores/
conv_attn_moe.py``) as functions of the configuration, counted by kind of
layer, for the readers that hold the ``lfm2-core`` cells to the chip's peak.
Beside ``costs_mla_moe.py``, whose count of the torso and the head around a
core, whose passes a step and whose count of the held experts' pairs
(``experts_flops``: 6 x hidden x width FLOPs a held pair a pass, the same
SwiGLU at this core's widths) it uses.

Counted: the matrix products the mathematics needs, at 2 FLOPs a
multiply-add. Not counted: norms, the gates' elementwise products and the
convolution's ``conv_L_cache`` taps (3 multiply-adds a channel, a
seven-thousandth of the operator's products), the rotation, the softmax, the
sort and gather of the routed pairs, Adam, and anything computed twice (the
layers are rematerialised in the backward pass; that is not model work).

In ``step_flops`` the held experts are counted at their expected share, a
position's ``num_experts_per_tok`` choices each falling on one of the
``experts_held`` of ``n_routed_experts`` with probability held / routed (one
pair a position at 4 x 8 / 32), as ``costs_mla_moe.py`` counts its own.
"""

from typing import Dict

from benchmarks.costs_mla_moe import (_torso_and_head_macs,  # noqa: F401
                                      experts_flops, passes)


def core_macs_per_position(core, in_dim: int, window: int) -> Dict[str, float]:
    """Multiply-adds of one forward pass of the whole stack for one window
    position, by part (the parts are the device trace's scopes). ``window``
    is the window's length T: a position's attention sees the ``memory_len``
    stored slots and, on average, (T + 1) / 2 of the window."""
    d, heads, groups = (core.hidden_size, core.num_attention_heads,
                        core.num_key_value_heads)
    e = d // heads
    convs = sum(kind == "conv" for kind in core.layer_types)
    attns = len(core.layer_types) - convs
    dense = core.first_k_dense_replace
    moe = core.num_hidden_layers - dense
    keys_seen = core.memory_len + (window + 1) / 2.0
    pairs = (core.num_experts_per_tok * core.experts_held
             / core.n_routed_experts)
    return {
        "input_proj": float(in_dim * d),
        # W_in: d -> 3d, W_out: d -> d
        "short_conv": convs * 4.0 * d * d,
        # q and the output over H heads, k and v over G, scores and the
        # weighted values over the keys a position sees
        "gqa_attn": attns * (2.0 * d * heads * e + 2.0 * d * groups * e
                             + keys_seen * heads * 2 * e),
        "dense_mlp": dense * 3.0 * d * core.intermediate_size,
        "moe_router": moe * float(d * core.n_routed_experts),
        "moe_experts": moe * pairs * 3.0 * d * core.moe_intermediate_size,
    }


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
