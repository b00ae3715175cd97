"""Replay data layout: fixed-shape block records and buffer state.

The reference stores ragged per-block numpy arrays in Python lists
(/root/reference/worker.py:69-78) and slices them with per-sample Python
loops (/root/reference/worker.py:140-166). XLA needs static shapes, so here a
block is a *fixed-shape record* — ragged reality is carried by per-sequence
metadata (burn_in/learning/forward/seq_start) and masks, and the unused tail
of a short block is zero padding that sampling can never select (its tree
leaves get priority 0).

Timeline convention for one block (matches the reference's indexing at
/root/reference/worker.py:143-149): position t in [0, burn_in0 + size) covers
the carried burn-in prefix then the block's new steps. ``obs_row[t + j]``
(j < frame_stack) is the stacked observation fed to the model at step t, with
``frame_stack - 1`` duplicate leading frames at episode start;
``last_action_row[t]`` is the action index taken at step t-1 (-1 = none, which
one-hot-encodes to the reference's zero vector, /root/reference/worker.py:416).
Sequence s starts at timeline ``seq_start[s] = burn_in0 + sum(learning[:s])``
and its sampled window begins at ``seq_start[s] - burn_in[s]``.
"""

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from flax import struct

from r2d2_tpu.config import Config
from r2d2_tpu.models.cores import state_half
from r2d2_tpu.ops.sum_tree import tree_num_layers


@dataclass(frozen=True)
class ReplaySpec:
    """Static shape/dtype contract shared by device and host replay, the
    actor-side block assembler, and the learner. Hashable → usable as a jit
    static argument."""

    num_blocks: int
    seqs_per_block: int     # S: sequence slots per block
    block_length: int       # steps per block
    burn_in: int            # max burn-in steps
    learning: int           # max learning steps per sequence (L)
    forward: int            # max n-step horizon (F)
    frame_stack: int
    frame_height: int
    frame_width: int
    # half the packed recurrent-state row (2, hidden_dim): the memory core's
    # ``state_half`` (models/cores/), the LSTM's width for the LSTM
    hidden_dim: int
    batch_size: int
    prio_exponent: float
    is_exponent: float
    # resolved at spec construction (ReplayConfig.pallas_sample_gather
    # tri-state): device-path sampling gathers obs windows with the pallas
    # kernel instead of the XLA gather
    pallas_gather: bool = False
    # ReplayConfig.pallas_exact_gather: pad stored frame height to a
    # sublane multiple and DMA only the sampled window (exact read,
    # async-copy kernel — used when pallas_gather is also on; without it
    # the row gather runs on the padded storage transparently, which is
    # how the CPU test path exercises the layout). The DEVICE obs ring and
    # sampled batches carry stored_frame_height rows; blocks, host replay,
    # and the decoded network input stay at frame_height.
    exact_gather: bool = False
    # Replay & data-pathology observability (ISSUE 10): True allocates the
    # in-graph diagnostic state on the replay ring (per-slot sample-count
    # ring, add-counter birth stamps, eviction accumulators) and routes
    # the sample/add paths through its accounting. Resolved from
    # telemetry.enabled AND telemetry.replay_diag_enabled — False (the
    # kill switch) compiles add/sample programs without any diagnostic
    # state, and the periodic record schema is byte-identical to PR9.
    replay_diag: bool = False

    @classmethod
    def from_config(cls, cfg: Config) -> "ReplaySpec":
        from r2d2_tpu.ops.pallas_kernels import resolve_pallas_setting
        return cls(
            num_blocks=cfg.num_blocks,
            seqs_per_block=cfg.seqs_per_block,
            block_length=cfg.replay.block_length,
            burn_in=cfg.sequence.burn_in_steps,
            learning=cfg.sequence.learning_steps,
            forward=cfg.sequence.forward_steps,
            frame_stack=cfg.env.frame_stack,
            frame_height=cfg.env.frame_height,
            frame_width=cfg.env.frame_width,
            hidden_dim=state_half(cfg.network),
            batch_size=cfg.replay.batch_size,
            prio_exponent=cfg.replay.prio_exponent,
            is_exponent=cfg.replay.importance_sampling_exponent,
            pallas_gather=resolve_pallas_setting(
                cfg.replay.pallas_sample_gather, "pallas_sample_gather"),
            exact_gather=resolve_pallas_setting(
                cfg.replay.pallas_exact_gather, "pallas_exact_gather"),
            replay_diag=(cfg.telemetry.enabled
                         and cfg.telemetry.replay_diag_enabled),
        )

    @property
    def stored_frame_height(self) -> int:
        """Frame height in the DEVICE obs ring under exact_gather: padded
        to the uint8 sublane-packing multiple so window slices are
        tile-aligned for the async-copy DMA; equal to frame_height
        otherwise. The obs ring is uint8, whose TPU tile is (32, 128) —
        1-byte values pack 4 rows per 4-byte sublane — so the pad multiple
        is 32 (84 -> 96), not the f32 tile's 8."""
        if not self.exact_gather:
            return self.frame_height
        return -(-self.frame_height // 32) * 32

    @property
    def stored_frame_width(self) -> int:
        """Frame width in the DEVICE obs ring under exact_gather: padded to
        the 128-lane tile. Mosaic requires BOTH minor dims of an HBM
        memref slice to be tile-aligned — an H-only pad was rejected on
        v5e ('slice along dimension 3 must be aligned to tiling (128), but
        is 84', builders, round 4). The decode strips the padding
        (stack_frames out_width), so the network still sees frame_width.

        STORAGE COST: the pad grows the whole obs ring 1.74x in HBM
        (96*128 vs 84*84 bytes per frame at reference scale) — the price
        of exact window reads. A production-capacity ring sized near the
        HBM limit can OOM at replay_init with exact_gather on; weigh that
        against the 7.7x -> 1.74x read-amplification win (PERF.md)."""
        if not self.exact_gather:
            return self.frame_width
        return -(-self.frame_width // 128) * 128

    @property
    def device_ring_bytes(self) -> int:
        """Estimated HBM footprint of one ReplayState at replay_init —
        exact for the arrays it allocates (obs ring dominating; padded
        dims under exact_gather). Used by the replay_init capacity guard
        so an oversized ring is refused with numbers instead of OOMing,
        and available to CLIs for config-time validation. Note
        dp-sharding does NOT divide this: each shard holds a full ring
        (sharded_replay_init)."""
        n, s, l = self.num_blocks, self.seqs_per_block, self.learning
        obs = (n * self.obs_row_len
               * self.stored_frame_height * self.stored_frame_width)
        last_action = n * self.la_row_len * 4
        hidden = n * s * 2 * self.hidden_dim * 4
        # action/reward/gamma (n,s,l) + 4 per-sequence i32 fields
        seq_meta = n * s * (3 * l + 4) * 4
        # per-block weight-version + lane-provenance stamps
        versions = 2 * n * 4
        tree = (2 ** self.tree_layers - 1) * 4
        # replay diagnostics (ISSUE 10): sample-count + birth-stamp rings,
        # the add counter, eviction accumulators, lifetime histogram
        diag = (2 * n + 1 + 5 + 64) * 4 if self.replay_diag else 0
        return obs + last_action + hidden + seq_meta + versions + tree + diag

    @property
    def seq_window(self) -> int:
        """Unrolled steps per sampled sequence (ref config.py:51 seq_len)."""
        return self.burn_in + self.learning + self.forward

    @property
    def obs_row_len(self) -> int:
        """Frames stored per block row. Covers the last sequence's full
        (padded) window: worst-case window start is burn_in0 + block_length -
        learning - burn_in, so the row must extend forward past the last
        learning step by the full ``forward`` horizon plus stacking margin."""
        return self.burn_in + self.block_length + self.forward + self.frame_stack - 1

    @property
    def la_row_len(self) -> int:
        return self.burn_in + self.block_length + self.forward

    @property
    def num_sequences(self) -> int:
        return self.num_blocks * self.seqs_per_block

    @property
    def tree_layers(self) -> int:
        return tree_num_layers(self.num_sequences)


class Block(struct.PyTreeNode):
    """One actor-produced block, fixed shape (device-ingestable as-is).

    The reference's 12-tuple (/root/reference/worker.py:86-91,492) with the
    ragged fields padded; ``sum_reward`` is NaN when no finished episode
    should be reported (reference uses None, /root/reference/worker.py:554-556).
    """

    obs_row: jnp.ndarray       # (obs_row_len, H, W) uint8
    last_action_row: jnp.ndarray  # (la_row_len,) int32, -1 = null
    hidden: jnp.ndarray        # (S, 2, hidden_dim) f32
    action: jnp.ndarray        # (S, L) int32
    reward: jnp.ndarray        # (S, L) f32 — n-step discounted returns
    gamma: jnp.ndarray         # (S, L) f32 — effective discount on bootstrap
    priority: jnp.ndarray      # (S,) f32 — initial |mixed TD|, 0 for empty slots
    burn_in_steps: jnp.ndarray  # (S,) int32
    learning_steps: jnp.ndarray  # (S,) int32 — 0 for empty slots
    forward_steps: jnp.ndarray  # (S,) int32
    seq_start: jnp.ndarray     # (S,) int32 — timeline offset of first learning step
    num_sequences: jnp.ndarray  # () int32
    sum_reward: jnp.ndarray    # () f32, NaN = do not report
    # Generation stamp for staleness accounting (ISSUE 5): the weight
    # service's PUBLISH COUNT the producing actor was acting with when it
    # emitted this block (stamped by instrument_block_sink). Trailing and
    # defaulted so pre-stamp (PR4-era) block records still construct —
    # -1 = unknown, reported as such rather than crashing.
    weight_version: jnp.ndarray = struct.field(
        default_factory=lambda: np.full((), -1, np.int32))  # () int32
    # Lane provenance (ISSUE 10): the GLOBAL ε-ladder lane index that
    # produced this block. Run loops stamp their lane-relative index and
    # instrument_block_sink offsets it to the fleet-global ladder position
    # (the on-device acting path stamps the global index in-graph). Same
    # trailing-defaulted pattern as the PR5 staleness stamp: PR5-era block
    # records without the field load as lane -1 = unknown.
    lane: jnp.ndarray = struct.field(
        default_factory=lambda: np.full((), -1, np.int32))  # () int32
    # Lineage trace stamp (ISSUE 19): wall-clock emission time in ms mod
    # 2^31 on the SAMPLED fraction of blocks a tracing run stamps
    # (telemetry.tracing_enabled + trace_sample_every). None-default —
    # NOT default_factory — so the leaf is absent from untraced blocks:
    # addw socket frames (the omit-None _block_fields contract), block
    # snapshots, and every compiled add program stay byte-identical with
    # tracing off, and pre-PR19 block records load as "untraced". The
    # replay service strips the leaf before device commit and carries
    # the stamp in the ring accountant's host mirrors instead.
    trace_ms: jnp.ndarray = None  # () int32, -1 = untraced


class ReplayState(struct.PyTreeNode):
    """Device-resident buffer state. Donated through jitted add/train steps so
    XLA updates it in place (no copy of the multi-GB obs ring)."""

    tree: jnp.ndarray          # (2**tree_layers - 1,) f32 priority sum tree
    obs: jnp.ndarray           # (N, obs_row_len, H, W) uint8
    last_action: jnp.ndarray   # (N, la_row_len) int32
    hidden: jnp.ndarray        # (N, S, 2, hidden_dim) f32
    action: jnp.ndarray        # (N, S, L) int32
    reward: jnp.ndarray        # (N, S, L) f32
    gamma: jnp.ndarray         # (N, S, L) f32
    burn_in_steps: jnp.ndarray  # (N, S) int32
    learning_steps: jnp.ndarray  # (N, S) int32
    forward_steps: jnp.ndarray  # (N, S) int32
    seq_start: jnp.ndarray     # (N, S) int32
    weight_version: jnp.ndarray  # (N,) int32 — per-block generation stamp
    block_ptr: jnp.ndarray     # () int32 ring pointer
    # Lane provenance ring (ISSUE 10): the producing ε-lane of each block
    # row (-1 = unknown / pre-stamp). Trailing + defaulted (a None leaf
    # drops from the pytree) so directly-constructed states in tests and
    # external pipelines keep working; replay_init always allocates it.
    lane: jnp.ndarray = None   # (N,) int32
    # -- replay-diagnostics state (ISSUE 10; allocated only under
    # spec.replay_diag — None leaves vanish from the pytree, so the kill
    # switch compiles the PR9 programs byte-for-byte) --
    sample_count: jnp.ndarray = None     # (N,) int32 — times any sequence
                                         # of the block was sampled
    added_at: jnp.ndarray = None         # (N,) int32 — add-counter value
                                         # when the block landed
    add_count: jnp.ndarray = None        # () int32 — monotonic adds
    # eviction accumulators, updated at overwrite in replay_add_many:
    # [evicted, never_sampled, lifetime_sum, age_sum,
    # final_priority_sum] — ages in ring adds (blocks), lifetimes in
    # times-sampled. SINCE-LAST-SNAPSHOT deltas: the diagnostics
    # snapshot (telemetry/replaydiag.fused_replay_diag) reads AND
    # resets them each interval, so the counts stay far below f32's
    # 2^24 exact-integer ceiling on runs of any length; cumulative
    # totals integrate host-side in float64 (ReplayDiagAggregator).
    evict_stats: jnp.ndarray = None      # (5,) float32
    # histogram (shared 64-bucket log layout) of times-sampled at
    # eviction, over evicted slots that WERE sampled (the never-sampled
    # count lives in evict_stats); reset with it
    evict_life_hist: jnp.ndarray = None  # (64,) int32


class SampleBatch(struct.PyTreeNode):
    """One training batch of sequences, still in storage dtypes (uint8 obs,
    index actions) — decode/normalize happens inside the train step where XLA
    fuses it into the conv (ref does /255 on GPU too, worker.py:330-331)."""

    obs: jnp.ndarray           # (B, seq_window + stack - 1, H, W) uint8
    last_action: jnp.ndarray   # (B, seq_window) int32
    hidden: jnp.ndarray        # (B, 2, hidden_dim) f32
    action: jnp.ndarray        # (B, L) int32
    reward: jnp.ndarray        # (B, L) f32
    gamma: jnp.ndarray         # (B, L) f32
    burn_in_steps: jnp.ndarray  # (B,) int32
    learning_steps: jnp.ndarray  # (B,) int32
    forward_steps: jnp.ndarray  # (B,) int32
    is_weights: jnp.ndarray    # (B,) f32
    idxes: jnp.ndarray         # (B,) int32 — tree leaf indices for write-back
    # (B,) int32 per-sequence generation stamp (the containing block's
    # weight_version; -1 = unknown). Trailing + defaulted: externally
    # assembled batches (tests, synthetic pipelines) that predate the
    # stamp keep constructing; a None leaf is dropped from the pytree, so
    # every jitted consumer that ignores it compiles unchanged.
    weight_version: jnp.ndarray = None
    # (B,) int32 producing ε-lane of each sequence (the containing
    # block's lane stamp; -1 = unknown) — same trailing-defaulted
    # contract as weight_version (ISSUE 10).
    lane: jnp.ndarray = None


class RingAccountant:
    """The single host-side authority for block-ring accounting: pointer
    advance, per-slot learning-step counts, total buffered steps, and the
    monotonic add counter behind the staleness guard.

    Exists so the wrap rule lives in ONE place (VERDICT r2 weak #5: the
    Learner, HostReplay, and the jitted replay_add each used to keep their
    own pointer arithmetic, consistent only by convention). HostReplay owns
    one; in host placement the Learner reads the SAME instance, and in
    device placement the Learner's instance is the host mirror of the
    compiled pointer in ReplayState.block_ptr (replay_add advances it with
    the identical `(ptr + 1) % num_blocks` rule — asserted equal in
    tests/test_replay.py)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self.ptr = 0
        self.total_adds = 0        # monotonic; never wraps
        self.slot_steps = [0] * num_blocks
        self.buffer_steps = 0      # live learning steps across the ring
        # per-slot generation stamp (the landed block's weight_version;
        # -1 = empty or unstamped) — the host mirror behind the learner's
        # replay-occupancy age percentiles (ISSUE 5)
        self.slot_versions = [-1] * num_blocks
        # lineage trace mirrors (ISSUE 19): the landed block's emission
        # stamp (Block.trace_ms, stripped before device commit) and the
        # wall-ms it was committed — both -1 for untraced slots, so an
        # untraced run's accounting is unchanged beyond two idle lists.
        self.slot_trace = [-1] * num_blocks
        self.slot_ingest_ms = [-1] * num_blocks

    def advance(self, learning_steps: int, weight_version: int = -1,
                trace_ms: int = -1, ingest_ms: int = -1) -> int:
        """Account one block write: returns the slot it lands in and rolls
        the pointer, replacing the overwritten slot's step count."""
        slot = self.ptr
        self.buffer_steps += learning_steps - self.slot_steps[slot]
        self.slot_steps[slot] = learning_steps
        self.slot_versions[slot] = int(weight_version)
        self.slot_trace[slot] = int(trace_ms)
        self.slot_ingest_ms[slot] = int(ingest_ms)
        self.ptr = (slot + 1) % self.num_blocks
        self.total_adds += 1
        return slot

    def live_versions(self):
        """Generation stamps of the slots currently holding data — the
        occupancy-age source (unstamped live slots report -1 = unknown)."""
        return [v for v, steps in zip(self.slot_versions, self.slot_steps)
                if steps > 0]

    def stale_adds(self, adds_snapshot: int) -> int:
        return self.total_adds - adds_snapshot


def empty_block_np(spec: ReplaySpec) -> dict:
    """Zeroed numpy block record (host-side assembly scratch)."""
    return dict(
        obs_row=np.zeros((spec.obs_row_len, spec.frame_height, spec.frame_width), np.uint8),
        last_action_row=np.full((spec.la_row_len,), -1, np.int32),
        hidden=np.zeros((spec.seqs_per_block, 2, spec.hidden_dim), np.float32),
        action=np.zeros((spec.seqs_per_block, spec.learning), np.int32),
        reward=np.zeros((spec.seqs_per_block, spec.learning), np.float32),
        gamma=np.zeros((spec.seqs_per_block, spec.learning), np.float32),
        priority=np.zeros((spec.seqs_per_block,), np.float32),
        burn_in_steps=np.zeros((spec.seqs_per_block,), np.int32),
        learning_steps=np.zeros((spec.seqs_per_block,), np.int32),
        forward_steps=np.zeros((spec.seqs_per_block,), np.int32),
        seq_start=np.zeros((spec.seqs_per_block,), np.int32),
        num_sequences=np.zeros((), np.int32),
        sum_reward=np.full((), np.nan, np.float32),
        weight_version=np.full((), -1, np.int32),
        lane=np.full((), -1, np.int32),
    )
