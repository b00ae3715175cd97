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

``lstm``: R2D2's LSTM (``HoistedLSTM``), the row is (h, c).
``mla_moe``: latent attention over a stored latent cache and a mixture of
experts of which this chip holds a share (``mla_moe.py``).
"""

from r2d2_tpu.config import NetworkConfig


def make_core(config: NetworkConfig, dtype):
    """The core ``config.core.kind`` names, computing in ``dtype``."""
    if config.core.kind == "lstm":
        from r2d2_tpu.models.cores.lstm import LSTMCore
        return LSTMCore(config, dtype)
    from r2d2_tpu.models.cores.mla_moe import MlaMoeCore
    return MlaMoeCore(config.core, dtype)


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
