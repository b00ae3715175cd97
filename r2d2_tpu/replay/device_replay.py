"""HBM-resident prioritized sequence replay — jitted add / sample / update.

The reference replay is a dedicated CPU process: numba sum-tree walks plus a
128-iteration Python slice loop per batch, reached through a Ray RPC
(/root/reference/worker.py:122-190). Here the whole buffer lives in HBM as
fixed-shape rings and all three operations are XLA programs:

  * ``replay_add``     — ring-write one block + seed its tree priorities
                         (ref worker.py:85-120);
  * ``replay_sample``  — stratified tree descent + batched dynamic-slice
                         gather of sequence windows (ref worker.py:122-190);
  * ``replay_update_priorities`` — write back learner TD priorities
                         (ref worker.py:192-209).

Because the learner fuses sample→train→update into ONE program, sampling and
its priority write-back are atomic with respect to block ingestion — the
reference's ring-pointer staleness guard (/root/reference/worker.py:196-206)
is unnecessary by construction: an ``add`` can never interleave between a
sample and its update.

All entry points donate the state argument, so XLA aliases the multi-GB obs
ring in place instead of copying it.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from r2d2_tpu.ops.sum_tree import tree_update, tree_sample
from r2d2_tpu.replay.structs import Block, ReplaySpec, ReplayState, SampleBatch


_PAD_WARN_BYTES = 2 << 30     # exact_gather pad warning floor (ADVICE r4)


def _guard_device_capacity(spec: ReplaySpec) -> None:
    """Refuse a ring that cannot fit in device memory with a clear,
    numeric message instead of OOMing mid-init (VERDICT r4 #3), and warn
    once when the exact_gather storage pad makes a large ring materially
    larger — the pad is easy to miss because the flag defaults on for TPU."""
    ring = spec.device_ring_bytes
    dev = jax.devices()[0]
    limit = None
    if dev.platform == "tpu":
        # the ONE memory_stats wrapper (telemetry/resources.py): same
        # backend-optional semantics — {} when the backend reports nothing
        from r2d2_tpu.telemetry.resources import device_memory_stats
        limit = device_memory_stats(dev).get("bytes_limit")
    if limit and ring > 0.9 * limit:
        hint = ""
        if spec.exact_gather:
            import dataclasses
            unpadded = dataclasses.replace(spec, exact_gather=False)
            hint = ("; replay.pallas_exact_gather='off' shrinks storage "
                    f"to ~{_gib(unpadded.device_ring_bytes)} (row-gather "
                    "reads instead of exact-window DMAs)")
        raise ValueError(
            f"device replay ring needs ~{_gib(ring)} but the device "
            f"reports {_gib(limit)} HBM — it would OOM at replay_init. "
            "Reduce replay.capacity or replay.block_length, use "
            f"replay.placement='host'{hint}.")
    if spec.exact_gather and ring > _PAD_WARN_BYTES:
        import warnings
        true_frame = spec.frame_height * spec.frame_width
        pad_frame = spec.stored_frame_height * spec.stored_frame_width
        warnings.warn(
            f"replay.pallas_exact_gather pads stored frames "
            f"{spec.frame_height}x{spec.frame_width} -> "
            f"{spec.stored_frame_height}x{spec.stored_frame_width} "
            f"({pad_frame / true_frame:.2f}x): the obs ring costs "
            f"~{_gib(ring)} in device memory. Set it 'off' for rings "
            "near the HBM limit.")


def _gib(b: float) -> str:
    return f"{b / 2**30:.1f} GiB"


def replay_init(spec: ReplaySpec) -> ReplayState:
    _guard_device_capacity(spec)
    n, s, l = spec.num_blocks, spec.seqs_per_block, spec.learning
    # replay diagnostics state (ISSUE 10): allocated only under the
    # pillar's kill switch — absent (None) leaves drop from the pytree,
    # so the compiled add/sample/step programs are byte-identical to the
    # pre-diagnostics ones when it is off
    diag = {}
    if spec.replay_diag:
        diag = dict(
            sample_count=jnp.zeros((n,), jnp.int32),
            added_at=jnp.zeros((n,), jnp.int32),
            add_count=jnp.zeros((), jnp.int32),
            evict_stats=jnp.zeros((5,), jnp.float32),
            evict_life_hist=jnp.zeros((64,), jnp.int32),
        )
    return ReplayState(
        tree=jnp.zeros(2**spec.tree_layers - 1, jnp.float32),
        # stored_frame_height/_width: tile-padded under spec.exact_gather
        obs=jnp.zeros((n, spec.obs_row_len, spec.stored_frame_height,
                       spec.stored_frame_width), jnp.uint8),
        last_action=jnp.full((n, spec.la_row_len), -1, jnp.int32),
        hidden=jnp.zeros((n, s, 2, spec.hidden_dim), jnp.float32),
        action=jnp.zeros((n, s, l), jnp.int32),
        reward=jnp.zeros((n, s, l), jnp.float32),
        gamma=jnp.zeros((n, s, l), jnp.float32),
        burn_in_steps=jnp.zeros((n, s), jnp.int32),
        learning_steps=jnp.zeros((n, s), jnp.int32),
        forward_steps=jnp.zeros((n, s), jnp.int32),
        seq_start=jnp.zeros((n, s), jnp.int32),
        weight_version=jnp.full((n,), -1, jnp.int32),
        block_ptr=jnp.zeros((), jnp.int32),
        lane=jnp.full((n,), -1, jnp.int32),
        **diag,
    )


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def replay_add(spec: ReplaySpec, state: ReplayState, block: Block) -> ReplayState:
    """Ring-write ``block`` at block_ptr and seed its sequence priorities.

    Empty sequence slots carry priority 0 (their leaves become unsamplable)
    and learning_steps 0, which also re-zeroes slots left over from a longer
    block previously in this ring position.

    Exactly the K=1 case of ``replay_add_many`` — one write path, so a
    Block/ReplayState field added to one cannot silently diverge from the
    other."""
    return replay_add_many(
        spec, state,
        jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], block))


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def replay_add_many(spec: ReplaySpec, state: ReplayState,
                    blocks: Block) -> ReplayState:
    """Ring-write K stacked blocks in ONE dispatch — parity-exact with K
    sequential ``replay_add`` calls, including ring wrap.

    ``blocks`` is a Block whose every leaf carries a leading K axis (the
    feeder's stacked drain). Block k lands in ring row
    ``(block_ptr + k) % num_blocks`` — the same rows the sequential path
    visits — and all K * seqs_per_block tree leaves are seeded by one
    ``tree_update``. Requires K <= num_blocks: beyond that the scatter rows
    alias (XLA scatter-set order over duplicates is undefined), and the
    sequential path's later-write-wins overwrite cannot be reproduced.
    K is a static shape, so each distinct drain size compiles once.
    """
    k = blocks.priority.shape[0]
    if k > spec.num_blocks:
        raise ValueError(
            f"replay_add_many got {k} blocks but the ring has only "
            f"{spec.num_blocks} rows — scatter rows would alias; cap "
            "replay.ingest_batch_blocks / fleet.ingest_batch_blocks "
            "(or the per-shard actor.anakin_lanes lane group) at "
            "num_blocks — note a sharded service ring has only "
            "num_blocks // fleet.replay_shards rows per shard")
    ptr = state.block_ptr
    rows = (ptr + jnp.arange(k, dtype=jnp.int32)) % spec.num_blocks
    idxes = (rows[:, None] * spec.seqs_per_block
             + jnp.arange(spec.seqs_per_block, dtype=jnp.int32)[None, :]
             ).reshape(-1)
    # eviction accounting (ISSUE 10): read the overwritten rows' lifetime
    # state BEFORE the tree update clobbers their leaf priorities. Rows
    # are distinct (k <= num_blocks, asserted above) so the batched read
    # sees exactly what K sequential adds would have seen row by row —
    # parity-tested against the sequential reference.
    diag = {}
    if spec.replay_diag and state.sample_count is not None:
        with jax.named_scope("replay_diag_evict"):
            live = (jnp.sum(state.learning_steps[rows], axis=1) > 0)  # (k,)
            counts = state.sample_count[rows].astype(jnp.float32)
            # row j is overwritten by the batch's j-th add, so its age is
            # measured against add_count + j — exactly the counter value
            # the sequential path would have seen (parity-tested)
            ages = (state.add_count + jnp.arange(k, dtype=jnp.int32)
                    - state.added_at[rows]).astype(jnp.float32)
            leaf0 = 2 ** (spec.tree_layers - 1) - 1
            prio_row = jnp.max(
                state.tree[leaf0 + idxes].reshape(k, spec.seqs_per_block),
                axis=1)
            livef = live.astype(jnp.float32)
            from r2d2_tpu.telemetry.histogram import value_counts
            diag = dict(
                sample_count=state.sample_count.at[rows].set(0),
                added_at=state.added_at.at[rows].set(
                    state.add_count + jnp.arange(k, dtype=jnp.int32)),
                add_count=state.add_count + k,
                evict_stats=state.evict_stats + jnp.stack([
                    jnp.sum(livef),
                    jnp.sum(livef * (counts == 0)),
                    jnp.sum(livef * counts),
                    jnp.sum(livef * ages),
                    jnp.sum(livef * prio_row)]),
                evict_life_hist=state.evict_life_hist + value_counts(
                    counts, mask=(live & (counts > 0)).astype(jnp.int32)),
            )
    tree = tree_update(spec.tree_layers, state.tree, spec.prio_exponent,
                       blocks.priority.reshape(-1), idxes)
    obs_rows = blocks.obs_row
    if (spec.stored_frame_height != spec.frame_height
            or spec.stored_frame_width != spec.frame_width):
        obs_rows = jnp.pad(obs_rows, (
            (0, 0), (0, 0),
            (0, spec.stored_frame_height - spec.frame_height),
            (0, spec.stored_frame_width - spec.frame_width)))
    return state.replace(
        tree=tree,
        obs=state.obs.at[rows].set(obs_rows),
        last_action=state.last_action.at[rows].set(blocks.last_action_row),
        hidden=state.hidden.at[rows].set(blocks.hidden),
        action=state.action.at[rows].set(blocks.action),
        reward=state.reward.at[rows].set(blocks.reward),
        gamma=state.gamma.at[rows].set(blocks.gamma),
        burn_in_steps=state.burn_in_steps.at[rows].set(blocks.burn_in_steps),
        learning_steps=state.learning_steps.at[rows].set(
            blocks.learning_steps),
        forward_steps=state.forward_steps.at[rows].set(blocks.forward_steps),
        seq_start=state.seq_start.at[rows].set(blocks.seq_start),
        weight_version=state.weight_version.at[rows].set(
            blocks.weight_version.astype(jnp.int32)),
        block_ptr=(ptr + k) % spec.num_blocks,
        **({"lane": state.lane.at[rows].set(
            blocks.lane.astype(jnp.int32))}
           if state.lane is not None else {}),
        **diag,
    )


def _gather_windows(spec: ReplaySpec, state: ReplayState,
                    block_idx: jnp.ndarray, window_start: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched gather of (obs, last_action) windows.

    window_start is the timeline offset ``seq_start - burn_in`` (>= 0 by
    construction of the block assembler); rows are padded so the full
    fixed-length window is always in bounds — no clamping can shift data.

    The obs gather is the dominant cost of sampling (52 MB of uint8 per
    batch); spec.pallas_gather routes it to the scalar-prefetch pallas
    kernel on TPU (2.6x the XLA gather, builders, round 3). last_action is 28 KB —
    the vmapped slice is fine everywhere."""
    from r2d2_tpu.ops.pallas_kernels import gather_rows
    obs_len = spec.seq_window + spec.frame_stack - 1
    obs = gather_rows(state.obs, block_idx, window_start, obs_len,
                      use_pallas=spec.pallas_gather,
                      exact_read=spec.exact_gather)

    def one_la(b, t0):
        return jax.lax.dynamic_slice(state.last_action[b], (t0,),
                                     (spec.seq_window,))

    return obs, jax.vmap(one_la)(block_idx, window_start)


@functools.partial(jax.jit, static_argnums=0)
def replay_sample(spec: ReplaySpec, state: ReplayState, key: jax.Array) -> SampleBatch:
    """Stratified prioritized sample of ``spec.batch_size`` sequences."""
    idxes, is_weights = tree_sample(
        spec.tree_layers, state.tree, spec.is_exponent, spec.batch_size, key)
    block_idx = idxes // spec.seqs_per_block
    seq_idx = idxes % spec.seqs_per_block

    burn_in = state.burn_in_steps[block_idx, seq_idx]
    learning = state.learning_steps[block_idx, seq_idx]
    forward = state.forward_steps[block_idx, seq_idx]
    seq_start = state.seq_start[block_idx, seq_idx]
    obs, last_action = _gather_windows(spec, state, block_idx, seq_start - burn_in)

    return SampleBatch(
        obs=obs,
        last_action=last_action,
        hidden=state.hidden[block_idx, seq_idx],
        action=state.action[block_idx, seq_idx],
        reward=state.reward[block_idx, seq_idx],
        gamma=state.gamma[block_idx, seq_idx],
        burn_in_steps=burn_in,
        learning_steps=learning,
        forward_steps=forward,
        is_weights=is_weights,
        idxes=idxes,
        weight_version=state.weight_version[block_idx],
        # lane provenance rides every batch (like weight_version); an
        # externally-built state without the ring field yields None and
        # consumers skip it
        lane=(state.lane[block_idx] if state.lane is not None else None),
    )


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def replay_update_priorities(spec: ReplaySpec, state: ReplayState,
                             idxes: jnp.ndarray, td_errors: jnp.ndarray
                             ) -> ReplayState:
    """Standalone priority write-back (host-driven pipelines). The fused
    learner step calls tree_update directly instead."""
    tree = tree_update(spec.tree_layers, state.tree, spec.prio_exponent,
                       td_errors, idxes)
    return state.replace(tree=tree)


def replay_size(state: ReplayState) -> jnp.ndarray:
    """Total stored learning steps (ref worker.py:81-82 __len__)."""
    return jnp.sum(state.learning_steps)
