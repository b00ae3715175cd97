"""R2D2's LSTM behind the core interface: the packed row is (h, c), each
``network.hidden_dim`` wide (the reference actor protocol's
``torch.cat(hidden_state)``)."""

import dataclasses
from typing import Any, Tuple

import jax.numpy as jnp

from r2d2_tpu.config import NetworkConfig


@dataclasses.dataclass(frozen=True)
class LSTMCore:
    config: NetworkConfig
    dtype: Any
    scope: str = "lstm"
    routes_experts = False

    @property
    def state_half(self) -> int:
        return self.config.hidden_dim

    def state_parts(self):
        return [("lstm_h_c", 1, 2 * self.config.hidden_dim)]

    @property
    def out_dim(self) -> int:
        return self.config.hidden_dim

    def init_state(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch, 2, self.state_half), jnp.float32)

    def unroll(self, x_seq: jnp.ndarray, state: jnp.ndarray,
               window_stats: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Time-batched LSTM with the input projection hoisted out of the
        scan (ref model.py:33 — torch nn.LSTM batch_first). It takes no
        statistic over a call's positions: ``window_stats`` changes
        nothing."""
        from r2d2_tpu.models.network import (HoistedLSTM, pack_hidden,
                                             unpack_hidden)
        cfg = self.config
        cell = HoistedLSTM(features=cfg.hidden_dim, dtype=self.dtype,
                           unroll=cfg.scan_unroll, name=self.scope)
        carry, outputs = cell(unpack_hidden(state.astype(self.dtype)), x_seq)
        return outputs, pack_hidden(carry).astype(jnp.float32)
