"""Operations of the ``kda_mla_moe`` memory core (``r2d2_tpu/models/cores/
kda_mla_moe.py``) as functions of the configuration, for the readers that
hold the ``kimi-linear-core`` cell to the chip's peak. Beside
``costs_mla_moe.py``, whose count of the torso and the head around a core
and whose passes a step it uses.

Counted: the matrix products the mathematics needs, at 2 FLOPs a
multiply-add: the projections, the chunked scan's own products (below), the
latent attention's scores and weighted values over the keys a position sees.
Not counted: the convolutions, norms, gates, decays, softmaxes, the sort and
gather of the routed pairs, Adam, and anything computed twice (the layers
are rematerialised in the backward pass, and the scan's backward kernel
recomputes its chunk's forward: time, not work).

The scan's products, per chunk of C positions, head and sequence (d_k =
d_v = e; the products over pairs (t, i) counted for the pairs that exist,
i < t or i <= t, C^2 / 2 each):

  forward:  K S_0, Q S_0, K^T U (3 C e^2); A and M (C^2 e); the solve for U
            and M U (C^2 e)
  backward: M^T dO, K dS, and the state's five products dO S_0^T, dY S_0^T,
            U dS^T, Q^T dO, K^T dY (7 C e^2); the solve for dY, dY U^T and
            dO U^T (3 C^2 e / 2); the pairs' four gradients (2 C^2 e)

In ``step_flops`` the held experts are counted at their expected share,
``num_experts_per_tok`` x ``experts_held`` / ``n_routed_experts`` pairs a
position, and the scan, like every other part, at ``passes`` forward passes
a step.
"""

from typing import Dict

from benchmarks.costs_mla_moe import _torso_and_head_macs, passes


def _scan_macs(core) -> Dict[str, float]:
    """Multiply-adds of the scan for one position of one KDA layer, all its
    heads: the forward's and the backward's (see the module's docstring)."""
    e, c = core.kda_head_dim, core.kda_chunk_size
    heads = core.kda_num_heads
    return {"forward": heads * (3.0 * e * e + 2.0 * c * e),
            "backward": heads * (7.0 * e * e + 3.5 * c * e)}


def core_macs_per_position(core, in_dim: int, window: int) -> Dict[str, float]:
    """Multiply-adds of one forward pass of the whole stack for one window
    position, by part (the parts are the device trace's scopes). A latent
    attention position sees the ``memory_len`` stored slots and, on
    average, (T + 1) / 2 of the window; the expansion of the stored slots'
    latents (once a sequence) is spread over its T positions."""
    d, heads = core.hidden_size, core.num_attention_heads
    dn, dr, dv, dc = (core.qk_nope_head_dim, core.qk_rope_head_dim,
                      core.v_head_dim, core.kv_lora_rank)
    e, width = core.kda_head_dim, core.kda_num_heads * core.kda_head_dim
    kda = core.layer_types.count("kda")
    mla = core.layer_types.count("full_attention")
    layers, dense = core.num_hidden_layers, core.first_k_dense_replace
    keys_seen = core.memory_len + (window + 1) / 2.0
    keys_expanded = (core.memory_len + window) / window
    expert = 3 * d * core.moe_intermediate_size
    pairs = (core.num_experts_per_tok * core.experts_held
             / core.n_routed_experts)
    return {
        "input_proj": float(in_dim * d),
        # q, k, v; the decay's two low-rank factors; beta
        "kda_proj": kda * float(3 * d * width + d * e + e * width
                                + d * core.kda_num_heads),
        "kda_scan": kda * _scan_macs(core)["forward"],
        # the output gate's two factors; W_o
        "kda_out": kda * float(d * e + e * width + width * d),
        "mla_attn": mla * float(d * heads * (dn + dr) + d * (dc + dr)
                                + keys_expanded * dc * heads * (dn + dv)
                                + keys_seen * heads * (dn + dr + dv)
                                + heads * dv * d),
        "dense_mlp": dense * 3.0 * d * core.intermediate_size,
        "moe_router": (layers - dense) * float(d * core.n_routed_experts),
        "moe_shared": (layers - dense) * float(expert * core.n_shared_experts),
        "moe_experts": (layers - dense) * pairs * expert,
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


def _scan_positions(cfg) -> float:
    """Positions the scan's kernels take a step, over the KDA layers: each
    window padded to whole chunks."""
    core = cfg.network.core
    chunk = core.kda_chunk_size
    padded = -(-cfg.sequence.seq_len // chunk) * chunk
    return float(cfg.replay.batch_size * padded
                 * core.layer_types.count("kda"))


def kda_scan_flops(cfg) -> float:
    """Model FLOPs the scan's kernels owe a train step: the forward of the
    online net and, under double-Q, of the target net, and the online
    net's backward, from the chunked algorithm's own products."""
    macs = _scan_macs(cfg.network.core)
    forwards = 2.0 if cfg.network.use_double else 1.0
    return 2.0 * _scan_positions(cfg) * (forwards * macs["forward"]
                                         + macs["backward"])


def kda_scan_bytes(cfg, itemsize: int = 2) -> float:
    """HBM bytes the scan's kernels move a train step, as they move them:
    q, k and v in the compute type (``itemsize``: bf16 on a TPU), the
    log-decays, beta, the output, the output's gradient float32, and a
    float32 e x e state for each chunk, which the forward writes and the
    backward reads; the backward writes the gradients of q, k, v, the
    log-decays and beta; forward passes as ``kda_scan_flops`` counts
    them."""
    core = cfg.network.core
    e, heads = core.kda_head_dim, core.kda_num_heads
    per = heads * e                       # one position's row of one array
    states = heads * e * e * 4.0 / core.kda_chunk_size
    # q, k, v, g in; o out; beta in
    forward = per * (3 * itemsize + 4 + 4) + heads * 4 + states
    # q, k, v, g, dO in; dq, dk, dv, dg out; beta in, its gradient out
    backward = per * (3 * itemsize + 4 + 4 + 3 * itemsize + 4) \
        + 2 * heads * 4 + states
    forwards = 2.0 if cfg.network.use_double else 1.0
    return _scan_positions(cfg) * (forwards * forward + backward)
