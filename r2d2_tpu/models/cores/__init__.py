"""The memory core between the conv torso and the dueling head.

A core is what carries an agent's memory from step to step. Every core
gives the same four things, and nothing outside this package knows which one
it is talking to:

  * ``state_half``: its recurrent state is one packed float32 row
    ``(2, state_half)`` a sequence. The replay ring, ``LocalBuffer``, the
    anakin scan's carry, the policy's and the server's state caches and the
    snapshot format all store that row and read its width from the core
    (``ReplaySpec.hidden_dim``);
  * ``out_dim``: the width of what it hands the head;
  * ``init_state(batch)``: the row an episode starts from (zeros);
  * ``unroll(x_seq, state, window_stats=False) -> (y_seq, state)``: a whole
    window ``(B, T, D_in)`` from a stored row. T = 1 is the actor's step.
    Called inside ``R2D2Network``'s compact ``__call__``, where it builds its
    flax modules under ``scope`` (the module name, which is also the
    device-trace scope and the parameter group's name). ``window_stats`` is
    the learner's call (``NetworkApply.apply_learner``): a statistic the
    core takes over positions comes from the call's own batch of windows,
    where acting reads what the train step stored among the parameters.

A core also says what the learner has to know of it, so that nothing asks
for a core by name: ``routes_experts`` (its layers route positions to
experts: the forward pass sows routing counters, the train step stores the
routers' input means among the parameters and the record carries a ``moe``
block; ``experts.py``) and ``state_parts()``, the state row by kind of part
as (kind, layers that keep such a part, floats in all of them), which the
record's ``core`` block carries (``state_block``). A core may sow, in the
learner's call, counters of its own per layer into the ``core`` collection,
among them ``loss``, a loss of its own at each window position, which the
learner adds to the TD loss (``learner/train_step.py``; a counter of shape
(layers, B, T), ``loss`` or another, is taken as its mean over the
learning positions, one of shape (layers,) as it is); such a core gives
``summarise(sums, steps)``, the record's blocks made of those counters
summed over steps, and may give
``record_block(batch, window)``, its own part of the one-shot ``core``
block for the learner's step (``sparse_attn_moe``: the indexer's sizes and
what its layers' remat keeps).

``lstm``: R2D2's LSTM (``HoistedLSTM``), the row is (h, c).
``mla_moe``: DeepSeek-V3-form layers: latent attention over a stored latent
cache and a mixture of experts of which this chip holds a share
(``mla_moe.py``).
``conv_attn_moe``: LFM2-MoE-form layers: gated short convolutions beside
grouped-query attention by ``layer_types``, each kind with its own stored
state (the convolution's last inputs, a window of keys and values) packed
layer by layer into the one row, and the same held experts with no shared
expert (``conv_attn_moe.py``). Its departures from the source stand in its
docstring and in ``benchmarks/configs/lfm2-core.json``.
``sparse_attn_moe``: Qwen3-MoE-form layers whose grouped-query attention
reads, for each query, the keys a learned indexer chooses (DeepSeek-V3.2's
sparse attention, Keye-VL-2.0's sizes), with the held experts routed by a
softmax (``sparse_attn_moe.py``). It is the first core with a loss of its
own: the indexer's, sown per position beside the attention's counters.
``kda_mla_moe``: Kimi-Linear-form layers: Kimi Delta Attention (a gated
delta rule, its e x e state a head carried in the row) beside the latent
attention of ``mla_moe`` in its NoPE form by ``layer_types``, with
``mla_moe``'s experts (``kda_mla_moe.py``). Its counters per KDA layer
(decays, beta, the state's norm) are sown per position into ``core`` with
no loss of its own, and the learner takes their means over its learning
positions as it takes a loss's.
The four stacks share ``experts.py``: router, held experts, counters.
"""

from typing import Any, Dict

from r2d2_tpu.config import NetworkConfig


def make_core(config: NetworkConfig, dtype):
    """The core ``config.core.kind`` names, computing in ``dtype``."""
    if config.core.kind == "lstm":
        from r2d2_tpu.models.cores.lstm import LSTMCore
        return LSTMCore(config, dtype)
    if config.core.kind == "conv_attn_moe":
        from r2d2_tpu.models.cores.conv_attn_moe import ConvAttnMoeCore
        return ConvAttnMoeCore(config.core, dtype)
    if config.core.kind == "sparse_attn_moe":
        from r2d2_tpu.models.cores.sparse_attn_moe import SparseAttnMoeCore
        return SparseAttnMoeCore(config.core, dtype)
    if config.core.kind == "kda_mla_moe":
        from r2d2_tpu.models.cores.kda_mla_moe import KdaMlaMoeCore
        return KdaMlaMoeCore(config.core, dtype)
    from r2d2_tpu.models.cores.mla_moe import MlaMoeCore
    return MlaMoeCore(config.core, dtype)


def state_block(config: NetworkConfig, batch: int,
                window: int) -> Dict[str, Any]:
    """The record's ``core`` block: the core's kind and its state row by
    kind of part (``state_parts``), so that a reader can tell what a
    sequence's stored row holds and how much of it; a core's
    ``record_block`` adds its own, sized for the learner's train step of
    ``batch`` windows of ``window`` positions."""
    import jax.numpy as jnp

    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_setting
    bf16 = resolve_pallas_setting(config.bf16, "network.bf16")
    core = make_core(config, jnp.bfloat16 if bf16 else jnp.float32)
    block = {"kind": config.core.kind, "state_half": core.state_half,
             "row_bytes": 8 * core.state_half,
             "parts": [{"kind": kind, "layers": layers, "floats": floats,
                        "bytes": 4 * floats}
                       for kind, layers, floats in core.state_parts()]}
    if hasattr(core, "record_block"):
        block.update(core.record_block(batch, window))
    return block


def require_lstm(config: NetworkConfig, what: str) -> None:
    """Paths that spell out the LSTM's own mathematics refuse other cores."""
    if config.core.kind != "lstm":
        raise NotImplementedError(
            f"{what} is written for the LSTM core; network.core.kind="
            f"{config.core.kind!r} runs on the single-chip fused learner "
            "step and the plain acting forward only")


def state_half(config: NetworkConfig) -> int:
    """Half the width of the packed state row of ``config``'s core."""
    import jax.numpy as jnp
    return make_core(config, jnp.float32).state_half
