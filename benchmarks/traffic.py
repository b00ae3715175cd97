"""The one traffic generator. For a training system the traffic is what the
replay ring holds when the learner samples it: a mix's data file
(``benchmarks/workloads/<mix>.json``, key ``replay``) gives the pool size,
the priority range and the reward scale; this module turns them, and the
seed, into blocks.

Blocks have the shape ``r2d2_tpu/replay/synthetic.py`` gives them (a full
block of S sequences with a carried burn-in prefix, the last sequence's
n-step horizon cut to 1 as at an episode's end), which is what
``LocalBuffer`` emits at the reference configuration. The pool is made on the
device in one jitted call, each block with priorities of its own, and stays
there: filling a 500,000-step ring is 1,250 ring writes of blocks the device
already holds, not 1,250 x 3.3 MB of host random numbers and copies.
"""

from typing import Any, Dict

import numpy as np


def make_block_pool(spec, action_dim: int, gamma: float,
                    params: Dict[str, Any], seed: int, sharding=None):
    """``pool_blocks`` seeded blocks, stacked on a leading axis, as a dict of
    ``Block`` fields on the device (``sharding`` places them: replicated over
    a mesh for a sharded ring, so that no write moves a block between
    chips)."""
    import jax
    import jax.numpy as jnp

    pool = int(params["pool_blocks"])
    lo, hi = params["priority_range"]
    s, l = spec.seqs_per_block, spec.learning
    burn = np.minimum(np.arange(s) * l, spec.burn_in).astype(np.int32)

    def tiled(x):
        return jnp.broadcast_to(jnp.asarray(x), (pool,) + np.shape(x))

    def build(key):
        k = jax.random.split(key, 7)
        return {
            "obs_row": jax.random.bits(
                k[0], (pool, spec.obs_row_len, spec.frame_height,
                       spec.frame_width), jnp.uint8),
            "last_action_row": jax.random.randint(
                k[1], (pool, spec.la_row_len), 0, action_dim, jnp.int32),
            # packed (h, c): h is a tanh output, c unbounded
            "hidden": jnp.stack([
                jnp.tanh(jax.random.normal(k[2], (pool, s, spec.hidden_dim))),
                jax.random.normal(k[3], (pool, s, spec.hidden_dim))], axis=2),
            "action": jax.random.randint(k[4], (pool, s, l), 0, action_dim,
                                         jnp.int32),
            "reward": float(params["reward_scale"]) * jax.random.normal(
                k[5], (pool, s, l), jnp.float32),
            "priority": jax.random.uniform(k[6], (pool, s), jnp.float32,
                                           lo, hi),
            "gamma": tiled(np.full((s, l), gamma ** spec.forward, np.float32)),
            "burn_in_steps": tiled(burn),
            "learning_steps": tiled(np.full((s,), l, np.int32)),
            "forward_steps": tiled(np.concatenate(
                [np.full((s - 1,), spec.forward), [1]]).astype(np.int32)),
            "seq_start": tiled((burn[0] + l * np.arange(s)).astype(np.int32)),
            "num_sequences": tiled(np.asarray(s, np.int32)),
            "sum_reward": tiled(np.asarray(np.nan, np.float32)),
            "weight_version": tiled(np.asarray(-1, np.int32)),
            "lane": tiled(np.asarray(-1, np.int32)),
        }

    return jax.jit(build, out_shardings=sharding)(jax.random.PRNGKey(seed))


# the three fields ``Learner.ingest`` reads on the host
HOST_READ = ("learning_steps", "sum_reward", "weight_version")


def fill_ring(learner, action_dim: int, params: Dict[str, Any], seed: int,
              sharding=None) -> int:
    """Fill every row of the learner's ring from the pool through
    ``Learner.ingest``, the program's own write path (2.8 ms of host a row on
    one chip), cycling through the pool; returns the rows written."""
    import jax
    from r2d2_tpu.replay.structs import Block

    pool = make_block_pool(learner.spec, action_dim, learner.cfg.optim.gamma,
                           params, seed, sharding)
    count = pool["priority"].shape[0]
    unstack = jax.jit(lambda tree: [jax.tree_util.tree_map(lambda x: x[i], tree)
                                    for i in range(count)])
    host = {k: np.asarray(pool[k][0]) for k in HOST_READ}
    blocks = [Block(**{**fields, **host}) for fields in unstack(pool)]
    rows = learner.ring.num_blocks
    for row in range(rows):
        learner.ingest(blocks[row % count])
    jax.block_until_ready(learner.replay_state.tree)
    return rows
