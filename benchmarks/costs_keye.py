"""Operations of the ``sparse_attn_moe`` memory core (``r2d2_tpu/models/cores/
sparse_attn_moe.py``) as functions of the configuration, for the readers that
hold the ``keye-core`` cell to the chip's peak. Beside ``costs_mla_moe.py``,
whose count of the torso and the head around a core, whose passes a step and
whose count of the held experts' pairs (``experts_flops``) it uses.

Counted: the matrix products the mathematics needs, at 2 FLOPs a
multiply-add: the projections, the indexer's scores over every key a query
sees, attention's scores and weighted values over the keys a query chose.
Not counted: norms, the rotation, the softmaxes, the threshold's bisection,
the heads' probabilities the indexer's loss recomputes and the loss itself,
the sort and gather of the routed pairs, Adam, and anything computed twice
(the layers are rematerialised in the backward pass).

A position's chosen keys are ``min(seen, index_topk)``, ``seen`` the stored
slots (``memory_len``, a full prefix) and the window up to itself; the held
experts are counted at their expected share, ``num_experts_per_tok`` x
``experts_held`` / ``n_routed_experts`` pairs a position.
"""

from typing import Dict

from benchmarks.costs_mla_moe import (_torso_and_head_macs,  # noqa: F401
                                      experts_flops, passes)


def keys_per_position(core, window: int):
    """(keys a window position sees, keys it chooses), each averaged over
    the window's positions, with a full stored prefix."""
    seen = [core.memory_len + t + 1 for t in range(window)]
    chosen = [min(s, core.index_topk) for s in seen]
    return sum(seen) / window, sum(chosen) / window


def core_macs_per_position(core, in_dim: int, window: int) -> Dict[str, float]:
    """Multiply-adds of one forward pass of the whole stack for one window
    position, by part (the parts are the device trace's scopes)."""
    d, heads, groups, e = (core.hidden_size, core.num_attention_heads,
                           core.num_key_value_heads, core.head_dim)
    j, c = core.index_n_heads, core.index_head_dim
    layers = core.num_hidden_layers
    dense = core.first_k_dense_replace
    seen, chosen = keys_per_position(core, window)
    pairs = (core.num_experts_per_tok * core.experts_held
             / core.n_routed_experts)
    return {
        "input_proj": float(in_dim * d),
        # q and the output over H heads, k and v over G
        "gqa_attn": layers * 2.0 * d * e * (heads + groups),
        # qI over J heads, kI and the J weights; the scores over every key
        # a position sees
        "dsa_indexer": layers * (d * (j * c + c + j) + seen * j * c),
        # scores and weighted values over the chosen keys, every head
        "dsa_attn": layers * chosen * heads * 2.0 * e,
        "dense_mlp": dense * 3.0 * d * core.intermediate_size,
        "moe_router": (layers - dense) * float(d * core.n_routed_experts),
        "moe_experts": (layers - dense) * pairs * 3.0 * d
        * core.moe_intermediate_size,
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


def sparse_attn_flops(cfg, pairs: float) -> float:
    """Model FLOPs the sparse attention kernel owes for ``pairs`` chosen
    (query, key) pairs of the online net's forward, summed over the layers:
    a pair's score and its weighted value in every query head (4 x H x e
    FLOPs), in every pass a step makes (``passes``; the target net is taken
    to choose as many pairs). Pairs a kernel's block holds beside the
    chosen, and the forward recomputed in the backward, are time, not
    work."""
    core = cfg.network.core
    return (4.0 * core.num_attention_heads * core.head_dim * passes(cfg)
            * pairs)
