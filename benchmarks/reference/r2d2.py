"""Plain float32 reference of the R2D2 loss, written from the published
description and independent of the code under test: no kernels, no bf16, no
fused anything, and none of the program's functions.

Kapturowski et al., "Recurrent Experience Replay in Distributed
Reinforcement Learning" (ICLR 2019), section 2.3 and the hyper-parameter
table; network after Mnih et al. 2015 (three convolutions and a dense layer)
with an LSTM and the dueling head of Wang et al. 2016:

  * observation t is ``stack`` consecutive frames, scaled to [0, 1];
  * torso: VALID convolutions with ReLU, flattened in (row, column, channel)
    order, one dense layer;
  * the LSTM reads [torso output, one-hot previous action] and is unrolled
    from the stored state over burn-in + learning + n-step positions;
  * Q = V + A - mean(A);
  * target for learning step j: h(R_j + gamma_j * h^-1(Q'(s_{j+n}, a*)))
    with h(x) = sign(x)(sqrt(|x| + 1) - 1) + eps x, where a* maximises the
    online net's Q and Q' is the target net's (double-Q) or the online net's
    own maximum; near an episode's end the position is clamped to the last
    valid one;
  * loss: importance-weighted half squared error over the valid learning
    steps; new priority: eta * max |td| + (1 - eta) * mean |td|.

Departures from the paper, all the program's (the fork's) and kept so that
the two can be compared: the dense layer after the convolutions has no ReLU;
the n-step return R_j and the discount gamma_j are stored by the actor and
read from the batch; adjacent sequences do not overlap, and the burn-in
prefix is the steps stored before the sequence.

Weights are the program's parameter tree (flax names), so both sides run the
same seeded weights; this file only reads arrays out of it.
"""

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp


def _h(x, eps):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def _h_inv(x, eps):
    root = (jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0) \
        / (2.0 * eps)
    return jnp.sign(x) * (root * root - 1.0)


def unroll_q(params: Dict[str, Any], frames, last_action, hidden, *,
             stack: int, strides: Sequence[int], dueling: bool):
    """Q for every position of every window.

    frames (B, T + stack - 1, H, W) uint8; last_action (B, T) int32, -1 for
    none; hidden (B, 2, D) packed (h, c). Returns (B, T, A) float32."""
    p = params["params"]
    b, t = last_action.shape
    f = frames.astype(jnp.float32) / 255.0
    # observation t = frames t .. t+stack-1, channels last
    obs = jnp.stack([f[:, k:k + t] for k in range(stack)], axis=-1)
    x = obs.reshape((b * t,) + obs.shape[2:])
    for i, stride in enumerate(strides):
        conv = p["torso"][f"Conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, conv["kernel"].astype(jnp.float32), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + conv["bias"]
        x = jnp.maximum(x, 0.0)
    dense = p["torso"]["Dense_0"]
    latent = x.reshape(b * t, -1) @ dense["kernel"] + dense["bias"]

    lstm = p["lstm"]
    actions = lstm["input_proj"]["kernel"].shape[0] - latent.shape[-1]
    one_hot = (last_action[..., None] == jnp.arange(actions)).astype(
        jnp.float32)
    x_seq = jnp.concatenate([latent.reshape(b, t, -1), one_hot], axis=-1)
    w_in, w_rec, bias = (lstm["input_proj"]["kernel"],
                         lstm["recurrent_kernel"], lstm["bias"])
    h, c = hidden[:, 0].astype(jnp.float32), hidden[:, 1].astype(jnp.float32)
    outs = []
    for step in range(t):
        gates = x_seq[:, step] @ w_in + h @ w_rec + bias
        i, f_, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f_) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        outs.append(h)
    hs = jnp.stack(outs, axis=1).reshape(b * t, -1)

    head = p["head"]

    def mlp(first, second):
        z = jnp.maximum(hs @ head[first]["kernel"] + head[first]["bias"], 0.0)
        return z @ head[second]["kernel"] + head[second]["bias"]

    adv = mlp("adv_hidden", "adv_out")
    q = adv
    if dueling:
        q = mlp("val_hidden", "val_out") + adv - adv.mean(-1, keepdims=True)
    return q.reshape(b, t, -1)


def loss_outputs(params, target_params, batch: Dict[str, Any], *,
                 frame_hw: Tuple[int, int], stack: int, strides: Sequence[int],
                 dueling: bool, double: bool, n_step: int, rescale_eps: float,
                 eta: float) -> Dict[str, Any]:
    """Loss, new priorities and the per-step values they are made of, for a
    batch in storage types (the fields of the program's ``SampleBatch``).

    ``tie_gap`` is, under double-Q, the distance between the online net's
    best two Q at t+n: a rounding difference flips an argmax whose gap it can
    close and swaps in another action's target value, so a comparison reads
    such steps apart. Without double-Q the maximum is taken, which no tie
    disturbs, and the gap is infinite."""
    hgt, wid = frame_hw
    frames = batch["obs"][:, :, :hgt, :wid]       # strip storage padding
    burn = batch["burn_in_steps"].astype(jnp.int32)[:, None]
    learn = batch["learning_steps"].astype(jnp.int32)[:, None]
    fwd = batch["forward_steps"].astype(jnp.int32)[:, None]
    steps = batch["action"].shape[1]
    j = jnp.arange(steps, dtype=jnp.int32)[None, :]
    valid = (j < learn).astype(jnp.float32)
    pos_now = burn + j
    pos_next = jnp.minimum(burn + n_step + j, burn + learn + fwd - 1)

    def pick(q, pos):                               # (B, T, A) at (B, L)
        return jnp.take_along_axis(q, pos[:, :, None], axis=1)

    kw = dict(stack=stack, strides=strides, dueling=dueling)
    q_online = unroll_q(params, frames, batch["last_action"],
                        batch["hidden"], **kw)
    q_next_online = pick(q_online, pos_next)        # (B, L, A)
    top2 = jnp.sort(q_next_online, axis=-1)[..., -2:]
    if double:
        q_target = unroll_q(target_params, frames, batch["last_action"],
                            batch["hidden"], **kw)
        best = jnp.argmax(q_next_online, axis=-1)
        q_next = jnp.take_along_axis(pick(q_target, pos_next),
                                     best[..., None], axis=-1)[..., 0]
        tie_gap = top2[..., 1] - top2[..., 0]
    else:
        q_next = top2[..., 1]
        tie_gap = jnp.full_like(valid, jnp.inf)
    target = _h(batch["reward"] + batch["gamma"] * _h_inv(q_next, rescale_eps),
                rescale_eps)
    q_chosen = jnp.take_along_axis(pick(q_online, pos_now),
                                   batch["action"][..., None], axis=-1)[..., 0]
    abs_td = jnp.abs(target - q_chosen) * valid
    count = jnp.maximum(valid.sum(), 1.0)
    loss = 0.5 * jnp.sum(batch["is_weights"][:, None] * abs_td ** 2) / count
    per_seq = jnp.maximum(valid.sum(axis=1), 1.0)
    priorities = (eta * jnp.max(jnp.where(valid > 0, abs_td, -jnp.inf), axis=1)
                  + (1.0 - eta) * abs_td.sum(axis=1) / per_seq)
    return {"loss": loss, "priorities": priorities, "q_chosen": q_chosen,
            "abs_td": abs_td, "valid": valid, "tie_gap": tie_gap}


def jitted_loss_outputs(**static):
    """``loss_outputs`` under ``jit`` with true float32 matrix products: on a
    TPU a float32 product runs in bf16 passes unless the precision is set."""
    def run(params, target_params, batch):
        with jax.default_matmul_precision("highest"):
            return loss_outputs(params, target_params, batch, **static)
    return jax.jit(run)


def from_config(cfg):
    """What every reference module gives the comparison (``check.py``):
    ``fn(params, target_params, batch fields) -> loss_outputs`` at the sizes
    of the program's ``Config``."""
    return jitted_loss_outputs(
        frame_hw=(cfg.env.frame_height, cfg.env.frame_width),
        stack=cfg.env.frame_stack,
        strides=tuple(stride for _, _, stride in cfg.network.conv_layers),
        dueling=cfg.network.use_dueling, double=cfg.network.use_double,
        n_step=cfg.sequence.forward_steps,
        rescale_eps=cfg.optim.value_rescale_eps, eta=cfg.optim.priority_eta)
