"""Typed, hierarchical configuration for the TPU-native R2D2 framework.

Replaces the reference's flat module of ~40 globals (/root/reference/config.py:1-62)
with an immutable dataclass tree. Every field keeps the reference default so the
stock Atari-Boxing / ViZDoom-Basic runs are a config-file change, not a code
change. Unlike the reference — where cross-module constants made the module the
single source of truth (/root/reference/worker.py:151-152) — components here take
their whole sub-config, so two differently-configured stacks can coexist in one
process (needed for multiplayer population training, /root/reference/train.py:28-45).

CLI overriding uses dotted paths (``--replay.capacity=100000``), covering the
genetic-search hook: the reference tags searchable fields ``<-- GEN``
(/root/reference/config.py:12-57); here they are enumerated in GENETIC_SEARCH_SPACE.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass(frozen=True)
class EnvConfig:
    """Environment selection and preprocessing (ref config.py:2-13)."""

    # Composed gym id, e.g. "VizdoomBasic-v0", "FakeR2D2-v0", "ALE/Boxing-v5".
    game_name: str = "Fake"
    env_type: str = "R2D2-v0"
    frame_stack: int = 4
    frame_height: int = 84
    frame_width: int = 84
    frame_skip: int = 1
    # Fixed episode length of the synthetic envs (Fake and the jitted
    # Grid/JaxFake backends — envs/jax_env.py); engine-backed envs ignore
    # it. The on-device acting path requires episode_len to be a multiple
    # of replay.block_length so episode boundaries coincide with block
    # boundaries (validated when actor.on_device is set).
    episode_len: int = 120
    # Grid side length of the jitted gridworld (env kind "Grid").
    grid_size: int = 6
    # The reference's factory defaults clip_rewards=True (environment.py:82)
    # but every call site passes False — actors (worker.py:507) and eval
    # (test.py:97) — relying on invertible value rescaling for reward
    # magnitudes instead. Match the effective behavior, not the dead default.
    clip_rewards: bool = False
    # Shaped multiplayer reward constants (ref base_gym_env.py:199-211).
    reward_hurt: float = -20.0
    reward_death: float = -100.0
    reward_ammo: float = -5.0
    reward_hit: float = 25.0
    reward_frag: float = 100.0

    @property
    def env_id(self) -> str:
        return self.game_name + self.env_type

    @property
    def obs_shape(self) -> Tuple[int, int, int]:
        return (self.frame_stack, self.frame_height, self.frame_width)


@dataclass(frozen=True)
class CoreConfig:
    """The memory core between the torso and the dueling head
    (``models/cores/``). ``kind="lstm"`` is the R2D2 LSTM of width
    ``network.hidden_dim`` and reads none of the other keys. The other two
    are stacks of a language model's layers whose keys are spelt as the
    source model's ``config.json`` spells them; a key that means the same in
    both sources is one key here.

    ``"mla_moe"``: DeepSeek-V3-form layers (latent attention, then a dense
    or a mixture-of-experts SwiGLU); the defaults are Moonlight-16B-A3B's
    (https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json).
    The source's keys that choose between forms (``q_lora_rank``,
    ``scoring_func``, ``topk_method``, ``n_group``, ``topk_group``,
    ``norm_topk_prob``, ``moe_layer_freq``) are not here: the core implements
    the one form that config names (models/cores/mla_moe.py).

    ``"conv_attn_moe"``: LFM2-MoE-form layers
    (https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json,
    ``model_type: lfm2_moe``), each a gated short convolution or
    grouped-query attention by ``layer_types`` ("conv" / "full_attention",
    one name a layer), then a dense or a mixture-of-experts SwiGLU
    (models/cores/conv_attn_moe.py). It reads ``layer_types``,
    ``conv_L_cache`` (the convolution's length L: a conv layer stores L - 1
    positions) and ``num_key_value_heads`` (heads are ``hidden_size /
    num_attention_heads`` wide) beside the shared keys; the source spells
    three of those otherwise: ``n_routed_experts`` is its ``num_experts``,
    ``first_k_dense_replace`` its ``num_dense_layers``, ``rms_norm_eps`` its
    ``norm_eps``. Its forms that are not options (sigmoid scores, the
    expert bias in the choice, normalised weights, no shared expert, no
    biases) are not keys; it reads none of the latent attention's keys
    (``kv_lora_rank``, ``qk_*_head_dim``, ``v_head_dim``) nor
    ``n_shared_experts``, and it has no defaults of its own: a
    configuration spells every size (benchmarks/configs/lfm2-core.json).

    ``"sparse_attn_moe"``: Qwen3-MoE-form layers whose grouped-query
    attention reads the keys a learned indexer chooses for each query
    (DeepSeek-V3.2's sparse attention), as Keye-VL-2.0-30B-A3B's
    (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
    ``model_type: KeyeVL2``, its ``sa_config``; models/cores/sparse_attn_moe.py).
    It reads ``num_key_value_heads`` and ``head_dim`` (heads of their own
    width, not ``hidden_size / num_attention_heads``), the indexer's
    ``index_n_heads`` x ``index_head_dim`` and ``index_topk`` (the source's
    ``indexer_num_heads``, ``indexer_head_dim``, ``topk``), and
    ``q_chunk_size`` / ``kv_chunk_size``, the blocks of queries and keys the
    attention is computed in; dense layers (``first_k_dense_replace``) are
    ``intermediate_size`` wide. Its own keys default to 0, which it refuses:
    a configuration spells every size (benchmarks/configs/keye-core.json).

    ``scoring_func`` is the form of every routed layer's scores, as the
    sources spell it: "sigmoid" (Moonlight, LFM2: the expert bias in the
    choice) or "softmax" over all routed experts (Qwen3-MoE, Keye: no bias;
    the chosen weights renormalised over the chosen, no epsilon).

    ``"kda_mla_moe"``: Kimi-Linear-form layers
    (https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
    ``model_type: kimi_linear``; models/cores/kda_mla_moe.py), each Kimi
    Delta Attention (a gated delta rule with a decay for each key channel)
    or the latent attention of ``mla_moe`` by ``layer_types`` ("kda" /
    "full_attention", one name a layer; the source's
    ``linear_attn_config.kda_layers`` and ``full_attn_layers``), then a
    dense SwiGLU (the first ``first_k_dense_replace`` layers) or the
    sigmoid-routed experts with ``n_shared_experts`` shared. It reads
    ``kda_num_heads`` x ``kda_head_dim`` and ``short_conv_kernel_size``
    (``linear_attn_config``'s ``num_heads``, ``head_dim``,
    ``short_conv_kernel_size``), ``kda_chunk_size`` (the positions of one
    chunk of the scan: a choice of compute, not a width), ``mla_use_nope``
    and ``q_chunk_size`` beside the latent attention's keys. Its own keys
    default to 0, which it refuses: a configuration spells every size
    (benchmarks/configs/kimi-linear-core.json).

    ``mla_use_nope`` (``mla_moe`` and ``kda_mla_moe``): the latent attention
    rotates neither q's nor k's ``qk_rope_head_dim`` dimensions (the
    source's NoPE form). ``q_chunk_size`` in those two kinds: where it is
    above 0 and below a window's length and divides it, the NoPE form
    computes its attention in blocks of that many queries, each block's
    scores recomputed in the backward pass (0, the default: all at once).

    Three keys are this program's own: ``experts_held`` / ``expert_offset``
    (the router scores all ``n_routed_experts`` and picks
    ``num_experts_per_tok`` of them; this chip computes the experts
    ``expert_offset .. expert_offset + experts_held - 1`` and leaves out what
    the others would add) and ``memory_len`` (positions of latent cache, or
    of keys and values, that the recurrent state carries for an attention
    layer)."""

    kind: str = "lstm"
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    first_k_dense_replace: int = 1
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.446
    experts_held: int = 64
    expert_offset: int = 0
    memory_len: int = 40
    layer_types: Tuple[str, ...] = ()
    conv_L_cache: int = 3
    num_key_value_heads: int = 8
    scoring_func: str = "sigmoid"
    head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    q_chunk_size: int = 0
    kv_chunk_size: int = 0
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    short_conv_kernel_size: int = 0
    kda_chunk_size: int = 0
    mla_use_nope: bool = False

    def __post_init__(self):
        # a JSON list (a checkpoint's .config.json) hashes as a tuple
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.kind not in ("lstm", "mla_moe", "conv_attn_moe",
                             "sparse_attn_moe", "kda_mla_moe"):
            raise ValueError(
                f"network.core.kind ({self.kind!r}) must be 'lstm', "
                "'mla_moe', 'conv_attn_moe', 'sparse_attn_moe' or "
                "'kda_mla_moe'")
        if self.kind == "lstm":
            return
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(
                f"network.core.scoring_func ({self.scoring_func!r}) must be "
                "'sigmoid' or 'softmax'")
        if not (0 <= self.expert_offset and self.experts_held >= 1
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"network.core: experts {self.expert_offset} .. "
                f"{self.expert_offset + self.experts_held - 1} are not "
                f"among the {self.n_routed_experts} routed experts")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError(
                "network.core.num_experts_per_tok exceeds n_routed_experts")
        if self.memory_len < 1 or self.num_hidden_layers < 1:
            raise ValueError(
                "network.core: memory_len and num_hidden_layers must be at "
                "least 1")
        if self.kind in ("mla_moe", "kda_mla_moe"):
            if self.qk_rope_head_dim % 2:
                raise ValueError(
                    "network.core.qk_rope_head_dim must be even")
            if self.q_chunk_size < 0:
                raise ValueError("network.core.q_chunk_size must be at "
                                 "least 0")
        if self.kind == "mla_moe":
            return
        if self.kind == "kda_mla_moe":
            if len(self.layer_types) != self.num_hidden_layers or any(
                    kind not in ("kda", "full_attention")
                    for kind in self.layer_types):
                raise ValueError(
                    f"network.core.layer_types ({self.layer_types!r}) must "
                    f"name each of the {self.num_hidden_layers} layers "
                    "'kda' or 'full_attention'")
            if min(self.kda_num_heads, self.kda_head_dim,
                   self.kda_chunk_size) < 1 \
                    or self.short_conv_kernel_size < 2:
                raise ValueError(
                    "network.core: kda_num_heads, kda_head_dim and "
                    "kda_chunk_size must be at least 1, "
                    "short_conv_kernel_size at least 2")
            return
        if self.kind == "sparse_attn_moe":
            heads, groups = self.num_attention_heads, self.num_key_value_heads
            if (heads < 1 or groups < 1 or heads % groups
                    or min(self.head_dim, self.index_head_dim) < 2
                    or self.head_dim % 2 or self.index_head_dim % 2):
                raise ValueError(
                    "network.core: num_key_value_heads must divide "
                    "num_attention_heads; head_dim and index_head_dim must "
                    "be even and at least 2")
            if min(self.index_n_heads, self.index_topk, self.q_chunk_size,
                   self.kv_chunk_size) < 1:
                raise ValueError(
                    "network.core: index_n_heads, index_topk, q_chunk_size "
                    "and kv_chunk_size must be at least 1")
            return
        if len(self.layer_types) != self.num_hidden_layers or any(
                kind not in ("conv", "full_attention")
                for kind in self.layer_types):
            raise ValueError(
                f"network.core.layer_types ({self.layer_types!r}) must name "
                f"each of the {self.num_hidden_layers} layers 'conv' or "
                "'full_attention'")
        heads, groups = self.num_attention_heads, self.num_key_value_heads
        if (heads < 1 or groups < 1 or heads % groups
                or self.hidden_size % heads
                or (self.hidden_size // heads) % 2):
            raise ValueError(
                "network.core: num_key_value_heads must divide "
                "num_attention_heads, which must divide hidden_size into "
                "heads of an even width")
        if self.conv_L_cache < 2:
            raise ValueError(
                "network.core.conv_L_cache must be at least 2 (a conv layer "
                "stores conv_L_cache - 1 positions)")


@dataclass(frozen=True)
class NetworkConfig:
    """Recurrent dueling/double DQN architecture (ref config.py:54-57, model.py:22-46)."""

    hidden_dim: int = 512
    cnn_out_dim: int = 1024
    use_dueling: bool = True
    use_double: bool = False
    # Conv torso: (out_channels, kernel, stride) triples — Nature DQN.
    conv_layers: Tuple[Tuple[int, int, int], ...] = ((32, 8, 4), (64, 4, 2), (64, 3, 1))
    # bf16 activation/compute policy (replaces torch.cuda.amp, ref
    # config.py:35; f32 params and f32 Q outputs either way). Tri-state:
    # "auto" (default) = bf16 iff the backend is TPU — the measured winner
    # there (+28% once the obs decode emits bf16 natively, PERF.md) —
    # while CPU keeps f32 (bf16 is emulated and slower). "on"/"off" force.
    # The MXU already multiplies in bf16 under f32 (default precision);
    # the policy additionally halves activation bytes, which is where the
    # win comes from. Loss parity vs f32 is tolerance-tested. Typed str
    # like the sibling pallas tri-states so --network.bf16=off works from
    # the CLI (resolve_pallas_setting still accepts legacy bools from old
    # serialized configs).
    bf16: str = "auto"
    # lax.scan unroll factor for the LSTM time scan (identical math; >1
    # trades compile time for fewer sequential loop boundaries on the
    # 55-step serial chain). Set from measurement — see PERF.md.
    scan_unroll: int = 1
    # -- quantized inference plane (ISSUE 14) --
    # Dtype of the ACTING/SERVING forward only (local scalar/vector
    # actors, the policy server's micro-batched dispatch, and the anakin
    # acting scan — all through the ONE shared forward); the learner's
    # training math is untouched. "f32" (default) = every existing
    # program byte-identical. "bf16" publishes a bf16 weight twin (2x
    # weight-bytes cut); "int8" publishes a per-channel symmetric int8
    # twin of every matmul kernel (~4x kernel-bytes cut), dequantized
    # per-channel into the compute-dtype matmul at apply time — the
    # acting forward is weight-streaming-bound at acting batch sizes
    # (costmodel tables; Podracer, arXiv 2104.06272), so cutting weight
    # bytes is the direct multiplier on env-steps/s and requests/s.
    # Quantization happens ONCE at weight publish (a quantized twin
    # rides the existing publish plumbing — no hot-path requantization);
    # the LSTM carry stays f32 so recurrent state never accumulates
    # quantization drift. Quality is guarded in-graph: a per-interval
    # probe runs the f32 twin on the live batch and feeds the record's
    # 'quant' block + the quant_divergence alert rule.
    inference_dtype: str = "f32"
    # The memory core (models/cores/): the LSTM by default. Nested, so that
    # whoever is handed ``cfg.network`` alone has the core's sizes too;
    # dotted overrides reach it as ``network.core.<key>``.
    core: CoreConfig = field(default_factory=CoreConfig)


@dataclass(frozen=True)
class SequenceConfig:
    """R2D2 sequence windowing (ref config.py:48-51)."""

    burn_in_steps: int = 40
    learning_steps: int = 10
    forward_steps: int = 5  # n-step return horizon

    @property
    def seq_len(self) -> int:
        return self.burn_in_steps + self.learning_steps + self.forward_steps


@dataclass(frozen=True)
class ReplayConfig:
    """Prioritized sequence replay (ref config.py:26-33, worker.py:38-78)."""

    capacity: int = 500_000          # env steps
    block_length: int = 400          # steps per actor-produced block
    prio_exponent: float = 0.9       # alpha; 0 disables prioritization
    importance_sampling_exponent: float = 0.6  # beta
    batch_size: int = 128            # sequences per training batch
    learning_starts: int = 1_000     # min buffer steps before training
    # Where replay lives: "device" = HBM-resident jitted path (the TPU-native
    # design), "host" = numpy + native C++ sum-tree feeder (reference-style).
    placement: str = "device"
    # Gather sampled obs windows with the pallas scalar-prefetch kernel
    # (ops/pallas_kernels.py gather_rows_pallas): "on", "off", or "auto"
    # (pallas iff the backend is TPU — 2.6x the XLA gather there, builders, round 3).
    pallas_sample_gather: str = "auto"
    # EXACT-read window gather (device placement): pad the stored frame to
    # the uint8 tile (84x84 -> 96x128) and DMA only each sampled window via
    # async copy instead of the whole ring row (7.7x read amplification at
    # the reference shape -> 1.74x). Measured WINNER on v5e: +4.2% on the
    # full fused step (90.7 vs 87.0 steps/s, builders, round 4) — hence "auto"
    # (= on iff TPU, like the sibling knobs). THE TRADE: storage also grows
    # 1.74x (6.6 vs 3.9 GiB of ring at the default 500k capacity), so a
    # ring sized near the HBM limit (~>1M frames on a 16 GiB chip) can OOM
    # at replay_init — set "off" there and keep the row-gather's 2.6x win.
    # Requires pallas_sample_gather; the stored obs layout changes with it.
    pallas_exact_gather: str = "auto"
    # Batched + pipelined ingestion (device placement): the learner's
    # stager thread coalesces up to this many actor blocks per drain into
    # ONE stacked host→device transfer + ONE jitted replay_add_many
    # dispatch, staged in the background so the transfer overlaps the
    # running train dispatch. -1 = auto (8 on TPU, where per-block host
    # dispatch and transfer sit on the learner's thread; 1 on CPU). 1 = the
    # legacy synchronous per-block path.
    # Capped by num_blocks (scatter rows must not alias).
    ingest_batch_blocks: int = -1
    # Max blocks the learner pops from the feeder queue per drain call —
    # ONE knob for both the training loop and the orchestrator's warm-up
    # loop (they used to hardcode 32 and 16 respectively).
    drain_max_blocks: int = 32
    # Reverb-style rate limiter: pause block ingestion (back-pressuring
    # actors through the bounded feeder queue) once
    # env_steps > learning_starts + ratio * train_steps. Pins the
    # data-collection : learning ratio so training dynamics do not depend
    # on the actors/learner scheduling balance of the host. 0 = unthrottled
    # (the reference's behavior: actors free-run, worker.py:528).
    max_env_steps_per_train_step: float = 0.0

    def resolved_ingest_batch_blocks(self) -> int:
        """-1 auto: batched ingestion (8 blocks/dispatch) iff the backend
        is TPU — there the per-block python dispatch + host-to-device
        transfer is a learner-loop cost; on CPU dispatch is cheap and the
        legacy per-block path stays the default."""
        if self.ingest_batch_blocks > 0:
            return self.ingest_batch_blocks
        import jax
        return 8 if jax.default_backend() == "tpu" else 1


@dataclass(frozen=True)
class OptimConfig:
    """Learner optimization (ref config.py:16-23, worker.py:268-269,341-346)."""

    lr: float = 1e-4
    adam_eps: float = 1e-3
    grad_norm: float = 40.0
    gamma: float = 0.997
    target_net_update_interval: int = 2_000
    training_steps: int = 500_000
    value_rescale_eps: float = 1e-2
    # Mixed-priority weights: eta*max + (1-eta)*mean (ref worker.py:246).
    priority_eta: float = 0.9
    # Decode uint8 obs windows with the fused pallas kernel
    # (ops/pallas_kernels.py): "on", "off", or "auto" (pallas iff the
    # backend is TPU — the measured winner there, builders, round 3; the XLA
    # gather path is the correct-everywhere fallback). Which kernel runs
    # follows from the shapes (decode_route): where the batch tiles the 128
    # lanes and storage is tile-padded (the exact gather's), the one that
    # writes the first convolution's own layout; the planar (B,T,K,H,W)
    # kernel + outer transpose for every other shape.
    pallas_obs_decode: str = "auto"


@dataclass(frozen=True)
class ActorConfig:
    """Ape-X actor fan-out (ref config.py:37-40, train.py:16-18)."""

    num_actors: int = 2
    base_eps: float = 0.4
    eps_alpha: float = 7.0
    actor_update_interval: int = 400   # steps between weight pulls (ref worker.py:568)
    max_episode_steps: int = 27_000
    near_greedy_eps: float = 0.02      # episode-return logging threshold (ref worker.py:555)
    # Env lanes per actor worker (envs/vector.py). 1 (default) = the legacy
    # single-env loop, byte-identical behavior. N>1 steps N envs through ONE
    # jitted (N, 1) policy forward per tick (actor/policy.py
    # BatchedActorPolicy) — the Podracer/GPU-emulation batching win (arxiv
    # 2104.06272, 1907.08467): actor cost goes from N interpreter+dispatch
    # round-trips per env step to one. The Ape-X ε ladder spreads over
    # num_actors * envs_per_actor total lanes (vector_lane_epsilons), so the
    # exploration schedule matches an equally-sized scalar-actor fleet.
    envs_per_actor: int = 1
    # -- Anakin-style fully on-device acting (runtime/anakin_loop.py) --
    # True routes training through the fused act+train loop: a jitted
    # lax.scan steps anakin_lanes batched PURE-JAX envs (envs/jax_env.py)
    # through the policy forward for block_length steps, assembles the
    # burn-in/learning blocks ON DEVICE, and ring-writes them straight
    # into device replay via replay_add_many — zero host transfers on the
    # acting hot path, weights read by reference from the colocated
    # learner's train state (Podracer "Anakin", arxiv 2104.06272). False
    # (default) = the legacy host actor fleet, byte-identical to pre-PR6.
    on_device: bool = False
    # Batched env lanes inside the fused acting scan — the GLOBAL count:
    # under a dp-wide mesh (mesh.dp > 1) the lanes partition into dp
    # equal per-shard groups (anakin_lanes % dp == 0), each acting into
    # its shard's local replay. Each segment emits one block per lane,
    # so the PER-SHARD group (lanes/dp) must be <= num_blocks (the
    # replay_add_many scatter-alias bound). The Ape-X ε ladder spreads
    # over the global lanes exactly like an equally-sized scalar-actor
    # fleet, regardless of dp.
    anakin_lanes: int = 64
    # Acting segments dispatched per train dispatch once training has
    # started (before learning_starts the loop acts continuously). >1
    # tilts the interleave toward collection — the fused loop is
    # synchronous, so this IS the collect:learn scheduling knob (the
    # replay rate limiter still applies on top).
    anakin_scans_per_train: int = 1
    # Initial priority of device-assembled sequences. A positive float
    # (default) stamps every sequence with that constant
    # (max-priority-style seeding) and lets the learner's first
    # write-back set the real priority. "td" computes the host path's
    # seeding IN-GRAPH instead: per-step n-step TD errors from the
    # acting policy's own Q-values (recorded along the scan + one extra
    # bootstrap forward per segment), mixed per sequence with
    # optim.priority_eta — fresh experience enters the tree already
    # ranked, at ~1/block_length extra acting compute.
    anakin_priority: Any = 1.0
    # Deterministic fault injection (tools/chaos.py): ';'-joined
    # ``slot:kind`` entries, e.g. "1:crash@block=3;2:hang@block=5;0:slowx4".
    # ``crash@block=N`` raises on the worker's N-th block emit (1-based),
    # ``hang@block=N`` wedges it there forever, ``slow@factor=F`` (or
    # ``slowxF``) stretches the interval between emits by F. Slots are
    # fleet-local worker indices (one fleet per host). "" (default) = no
    # faults. Exists so every health behavior — watchdog kill, backoff,
    # breaker, ring reclamation — is exercised by real misbehaving workers
    # in tests and in the soak's chaos phase, not just hoped for.
    # With inference="server" two CLIENT-side kinds join (ISSUE 13):
    # ``disconnect@req=N`` drops the worker's serve connection every N-th
    # request (exercising lease release + reconnect-with-state), and
    # ``slow``/``slowxF`` moves from the block sink to the request path
    # (stretching the worker's request cadence — a laggy client against
    # the micro-batcher). crash/hang stay at the block sink either way.
    fault_spec: str = ""
    # Where the acting forward runs (ISSUE 13): "local" (default) = the
    # policy + its recurrent state live in the actor worker (pre-PR13
    # behavior, byte-identical); "server" = the worker holds a thin
    # RemotePolicy and the central policy server (r2d2_tpu/serve/) owns
    # params + per-client state, micro-batching all workers' requests
    # into one device forward — the SEED placement (arXiv 1910.03552).
    # Action parity at equal seeds/ε is test-asserted.
    inference: str = "local"


@dataclass(frozen=True)
class ServeConfig:
    """Central policy inference service (ISSUE 13; r2d2_tpu/serve/):
    a SEED-style batched policy server — thin clients submit raw
    observation frames, one server loop owns the device-resident params
    and a sharded per-client LSTM-state + frame-stack cache, and
    micro-batches pending requests into one jitted forward under a
    latency deadline. ``actor.inference="server"`` routes the existing
    actor loops through it; ``cli/serve.py`` runs it standalone;
    ``cli/evaluate.py --serve`` is evaluation-as-a-service."""

    # Micro-batch dispatch bound: a batch dispatches when it holds this
    # many requests OR when the oldest pending request is deadline_ms
    # old, whichever first. Dispatch widths pad to power-of-two buckets
    # (all pre-compiled at server start) so fill jitter never retraces.
    max_batch: int = 32
    deadline_ms: float = 5.0
    # Serving fleet width (ISSUE 17): 1 (default) = the single PR-12
    # server loop, byte-identical. >= 2 = N server loops behind the
    # client-side router (serve/router.py), each owning a contiguous
    # slice of the state cache's shard groups; a request routes by
    # client_id % state_shards and never crosses servers. Thread-mode
    # actors ride in-proc endpoints; process-mode actors and cli/serve.py
    # ride one socket listener per server (the shm rung stays
    # single-server).
    servers: int = 1
    # Maximum fleet width (grow_server headroom): 0 (default) = servers
    # (no spare server slots). Spare slots pre-create their endpoints/
    # listeners so remote clients know every address up front; a grown
    # server attaches to its persistent endpoint (the PR-12 restart
    # pattern, now per slot).
    max_servers: int = 0
    # Admission control / brownout (ISSUE 17): a server whose inbox
    # backlog exceeds this many requests AFTER filling a dispatch sheds
    # the excess with STATUS_RETRY (+ retry_after hint) instead of
    # letting batch_wait run away — shed clients back off on the
    # WorkerHealth ladder and resend (the op was NOT applied). 0
    # (default) = no admission control, byte-identical records.
    queue_depth_bound: int = 0
    # State cache geometry: total per-client slots (each holds one packed
    # LSTM hidden + rolling frame stack + last action) partitioned into
    # ``state_shards`` independently-leased shard groups (client ids hash
    # onto shards; the layout a multi-device server pins per device).
    state_slots: int = 1024
    state_shards: int = 4
    # A DISCONNECTED client's state survives this long before eviction —
    # the reconnect window (a bouncing client resumes mid-episode); an
    # evicted slot resets to the episode-initial zero state.
    lease_timeout_s: float = 120.0
    # Client-side request timeout: past it the client backs off on the
    # PR-3 WorkerHealth ladder, reconnects, and resends; after
    # ``max_retry_s`` of failures it raises (worker supervision takes
    # over: respawn with backoff).
    request_timeout_s: float = 5.0
    max_retry_s: float = 60.0
    # Server-side request TTL: requests older than this at dispatch are
    # dropped unapplied (a restarted server must not replay its dead
    # predecessor's backlog — the client already timed out and will
    # resend its current state). 0 disables.
    request_ttl_s: float = 10.0
    # Transport rung for PROCESS-mode actors: "shm" (the shm_feeder ring
    # discipline — native MPMC request ring + per-client reply rings),
    # "socket" (TCP, the cross-host rung), or "auto" (shm when the
    # native toolchain is available, else socket). Thread-mode actors
    # always ride the in-proc queue; cli/serve.py listens on socket
    # (and shm with --shm).
    transport: str = "auto"
    host: str = "127.0.0.1"
    port: int = 0                   # 0 = ephemeral (socket transport)
    # Ring geometries (shm transport).
    request_ring_slots: int = 256
    reply_ring_slots: int = 16
    # Seconds between the server's weight-service polls (the reader side
    # of runtime/weights.py; every reply stamps the adopted publish
    # count so block staleness accounting stays live in served mode).
    weight_poll_interval_s: float = 1.0
    # Pre-compile every pow2 dispatch bucket at server start (the ingest
    # stager's AOT recipe — a lazy mid-run compile parks every client).
    warmup: bool = True
    # Shadow mirroring (ISSUE 20): fraction of live OK step replies the
    # client-side router copies to a candidate server for divergence
    # scoring (fleet/promotion.py ShadowScorer — mirrored replies are
    # never returned to clients). 0 (default) = no mirror sink is ever
    # attached; the routing path is byte-identical to PR-17.
    shadow_sample_rate: float = 0.0


@dataclass(frozen=True)
class FleetConfig:
    """Elastic fleet control plane (ISSUE 15; r2d2_tpu/fleet/): the
    disaggregated replay service with its host-RAM spill tier, the
    weight fan-out relay tree, and live actor join/leave. Every field's
    default leaves the pre-PR15 plumbing byte-identical (no service, no
    relays, frozen fleet)."""

    # Replay service (fleet/replay_service.py): 0 (default) = the legacy
    # in-mesh replay (single ring or dp-sharded, byte-identical). >= 1 =
    # the learner routes ingestion through a ReplayService of this many
    # addressable shards (device capacity num_blocks/replay_shards rows
    # each) and trains through the external-batch step on
    # service-sampled batches — the disaggregated plane any producer
    # (local feeder, remote socket rung) can route blocks into.
    replay_shards: int = 0
    # Host-RAM spill tier, PER SHARD, in blocks: a device ring-write
    # that overwrites a live block demotes its host page into an LRU
    # page store of this capacity instead of destroying it; pages
    # rotate back into the samplable ring at sample time. Total
    # effective capacity = device rings + spill (the >= 2x-HBM-budget
    # acceptance). 0 = no spill (overwrite semantics unchanged).
    spill_blocks: int = 0
    # Spilled pages rotated back into the device ring per sample call
    # (the promote-on-sample-hit cycle). 0 disables re-promotion (the
    # spill tier becomes a pure archive until it evicts).
    spill_promote_per_sample: int = 1
    # Block -> shard routing: "round_robin" (the dp-sharded path's
    # feeding order — what the service-vs-in-mesh parity test pins) or
    # "lane" (shard = lane-provenance stamp % shards: a producer's
    # blocks land by lane identity, so shard contents are
    # provenance-checkable and a joiner adopting a slot's lanes adopts
    # its routing — the churn drill's setting).
    replay_route: str = "round_robin"
    # Expose the service to REMOTE producers over the socket rung
    # (fleet/replay_service.py ReplayServiceServer): "" (default) = off;
    # "socket" = listen on service_host:service_port.
    service_transport: str = ""
    service_host: str = "127.0.0.1"
    service_port: int = 0           # 0 = ephemeral
    # Weight fan-out tree (fleet/fanout.py): 0 (default) = every actor
    # polls the one publisher/store directly (pre-PR15). >= 2 = relay
    # tree of this degree — the learner publishes once, relay nodes
    # re-publish, actors read leaf relays (thread mode: in-proc relays;
    # process mode + multihost hosts: shm relay segments). The stamped
    # quant bundle rides through relays unchanged.
    fanout_degree: int = 0
    # In-proc relays pull upstream on this interval instead of being
    # pushed per publish; 0 (default) = push-through on every publish
    # (zero steady-state lag). Nonzero makes relay lag real — the
    # fanout_lag alert's test hook and the cadence knob for
    # pull-through deployments.
    fanout_pull_interval_s: float = 0.0
    # Maximum fleet width for elastic membership: 0 (default) =
    # actor.num_actors (no spare slots). > num_actors reserves
    # (max_slots - num_actors) FREE spare slots joiners can lease
    # mid-training; the ε ladder and lane ranges span max_slots so the
    # exploration schedule is fixed as the fleet churns.
    max_slots: int = 0
    # Elastic supervision policy: False (default) = a dead actor is
    # respawned in place on the PR-3 backoff ladder (pre-PR15). True = a
    # dead/left actor's slot PARKS for re-adoption (membership.park) and
    # training continues on the remaining fleet — the join/leave drill's
    # setting; re-admission goes through PlayerStack.join_actor.
    elastic: bool = False
    # -- batched/pipelined service data plane (ISSUE 16) --
    # Blocks the service commits per jitted dispatch: 1 (default) = the
    # PR-15 per-block replay_add path, byte-identical. K > 1 = the
    # learner's service drain stacks up to K queued blocks and
    # ReplayService.add_blocks groups them by routed shard, committing
    # each group through the donated replay_add_many program
    # (pow2-bucketed, AOT-precompiled at service start) — bit-identical
    # contents to K sequential adds, one dispatch instead of K.
    ingest_batch_blocks: int = 1
    # In-flight frame window for the socket rung's producer: 1 (default)
    # = PR-15's one-frame-one-ack lockstep (a full RTT per frame). W > 1
    # = RemoteReplayProducer keeps up to W unacked frames in flight
    # (cumulative acks, back-pressure at the window bound) so remote
    # producers stop paying a blocking round-trip per block.
    socket_window: int = 1
    # Priority-aware async spill promotion: False (default) = PR-15's
    # inline LRU rotation inside the sample call. True = spilled pages
    # promote by STORED priority (max-heap over each page's leaf
    # priorities) and promotion is kicked asynchronously at write-back
    # time, so the sample path stops paying promotion latency inline.
    spill_prefetch: bool = False
    # Service-mode sample staging: False (default) = the fully
    # synchronous PR-15 service step (sample -> train -> write-back on
    # one thread). True = the PR-2 stager treatment for the service
    # path: a staging thread drains the next per-shard sample batch
    # while the train dispatch runs, and priority write-backs batch per
    # sampled shard on a writeback thread (the PR-14 staleness guard
    # applies per entry, now reaching spilled pages too).
    sample_staging: bool = False
    # Fleet lease API (ISSUE 17, ROADMAP 2c): "" (default) = joins are
    # in-process only (PlayerStack.join_actor). "socket" = the
    # orchestrator listens on lease_host:lease_port
    # (fleet/membership.py MembershipServer) and a FRESH process joins
    # the running fleet through cli/join.py — it leases a slot over the
    # wire, adopts the slot's identity, routes blocks in via the replay
    # service's socket rung, and reaches served inference through the
    # serve fleet's socket listeners.
    lease_transport: str = ""
    lease_host: str = "127.0.0.1"
    lease_port: int = 0             # 0 = ephemeral
    # -- gated canary promotion (ISSUE 20; fleet/promotion.py) --
    # Eval-return gate: a candidate promotes only if its per-scenario
    # mean return >= the live policy's minus this tolerance (absolute,
    # in return units — returns are env-scale, not normalized).
    promotion_return_tolerance: float = 0.05
    # Calibration gate: |mean (predicted max-Q − realized n-step
    # return)| of the candidate's stream must stay under this bound
    # (fail-open when no calibration stream exists — process fleets).
    promotion_calibration_bound: float = 10.0
    # Shadow gate: greedy-disagreement fraction on mirrored traffic
    # must stay under this bound, measured over at least
    # promotion_min_shadow scored requests (fail-closed below the
    # minimum — a promotion must earn its evidence).
    promotion_divergence_bound: float = 0.25
    promotion_min_shadow: int = 32
    # Fraction of fan-out consumers a staged candidate canary-publishes
    # to (leaf-relay granularity; 0 disables the canary slice — the
    # candidate proves itself on shadow + eval alone).
    promotion_canary_frac: float = 0.25

    def resolved_max_slots(self, num_actors: int) -> int:
        return self.max_slots if self.max_slots > 0 else num_actors

    @property
    def active(self) -> bool:
        """Any fleet plane configured on — gates the record's
        replay_service block so legacy runs keep a byte-identical
        schema."""
        return (self.replay_shards > 0 or self.fanout_degree > 0
                or self.max_slots > 0 or self.elastic)


@dataclass(frozen=True)
class MultiplayerConfig:
    """Population self-play (ref config.py:43-45, train.py:28-45)."""

    enabled: bool = False
    num_players: int = 2
    base_port: int = 5060
    # -1 (default): this process trains the WHOLE population in one job
    # (the reference's train.py model; single-host orchestrator only).
    # >= 0: this job trains exactly ONE player of the population — the
    # per-player-job composition that scales multiplayer to pods (one
    # multihost job per player; players interact only through the game
    # engine's host/join sockets, never through collectives — README
    # "Multiplayer at pod scale"). Player 0's actors host the games on
    # port(actor_idx); every other player's actor i joins game i.
    player_id: int = -1

    def port(self, actor_idx: int) -> int:
        return self.base_port + actor_idx

    def env_args(self, player_idx: int, actor_idx: int) -> dict:
        """Host/join wiring for one actor's env (ref train.py:33-38) —
        shared by the single-host orchestrator and the per-player-job
        multihost trainer so the two paths cannot drift."""
        if not self.enabled:
            return dict(is_host=False, port=self.base_port)
        return dict(is_host=player_idx == 0, port=self.port(actor_idx))


@dataclass(frozen=True)
class MeshConfig:
    """TPU device-mesh layout for the learner.

    The reference has no learner parallelism (one process on half a GPU,
    ref worker.py:251); here data-parallel over the 'dp' axis (batch sharded,
    gradient psum over ICI) and model-parallel over 'mp' (hidden/cnn feature
    sharding) are first-class. A 1x1 mesh degrades to single-chip.
    """

    # 1 = single-chip (default); N>1 = dp-shard the learner over N chips;
    # -1 = all available devices. The runtime Learner builds the shard_map
    # step + sharded replay whenever the resolved mesh is wider than one
    # device (runtime/learner_loop.py).
    dp: int = 1
    mp: int = 1

    def resolved_dp(self, n_devices: int) -> int:
        mp = max(self.mp, 1)
        return self.dp if self.dp > 0 else max(n_devices // mp, 1)
    # Multi-host: initialize jax.distributed (DCN) before mesh construction.
    multihost: bool = False
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0


@dataclass(frozen=True)
class TelemetryConfig:
    """Unified runtime telemetry (r2d2_tpu/telemetry/): percentile stage
    timers, span tracing, cross-process aggregation. On by default — the
    benched overhead budget is < 2% env-steps/s (tools/e2e_bench.py
    --telemetry-ab; PERF.md "Telemetry overhead")."""

    # Master kill-switch: false turns every telemetry entry point into a
    # cheap no-op (stage observes, span records, board publication, the
    # aggregated 'stages' block in the periodic record).
    enabled: bool = True
    # Span ring capacity PER THREAD (spans.py). When a drain interval
    # overflows it the oldest spans drop (counted, surfaced as
    # telemetry_dropped_spans in the periodic record) — sized for block
    # cadence, not per-env-step events.
    ring_size: int = 4096
    # Drain cadence: spans ring -> spans_*.jsonl, and worker histogram
    # counts -> the shared-memory board.
    flush_interval_s: float = 5.0
    # Span tracing sub-switch: histograms stay on (they are the
    # aggregated record's source); spans cost a JSONL file per process.
    spans: bool = True
    # -- learning-dynamics diagnostics (telemetry/learning.py, ISSUE 5) --
    # Kill switch for the learner-side LEARNING diagnostics fused into the
    # jitted train step: |TD|/priority/Q histograms, per-group gradient
    # norms, target-network parameter distance, the stored-state ΔQ
    # check, sample-age staleness, and NaN forensics. Off (or with the
    # master `enabled` off) the train step compiles WITHOUT any
    # diagnostic outputs — the hot path is byte-identical to pre-PR5.
    learning_enabled: bool = True
    # Learner steps between ΔQ / target-distance evaluations (lax.cond
    # inside the jitted step: the extra unrolls only execute on interval
    # steps, so the steady-state cost is amortized to ~nothing).
    learning_interval: int = 200
    # Sequences per ΔQ evaluation (the full-context reference unroll runs
    # over the whole stored block row — ~8x the window length — so this
    # sub-batch bounds its transient activation memory; 16 ≈ one training
    # batch's activation footprint at the reference shape).
    learning_dq_batch: int = 16
    # What to do when the train step's loss/grad-norm first goes
    # non-finite (detected at the metrics flush): both policies write a
    # one-shot nan_dump_player{p}.json forensic record; "warn" logs and
    # continues (the reference's silent-NaN failure mode, made loud),
    # "halt" raises after the dump so the run stops at the poisoned step.
    nan_policy: str = "warn"
    # -- resource & compilation observability (ISSUE 7) --
    # Pillar kill switch: per-device memory_stats sampling, buffer
    # attribution, host/actor RSS+CPU, the compile/retrace telemetry, and
    # the record's 'resources' + 'alerts' blocks. False (or the master
    # `enabled` off) yields periodic records byte-identical to the
    # pre-PR7 schema (stability-tested).
    resources_enabled: bool = True
    # Seconds between resource samples (a handful of dict reads and one
    # /proc line — benched within noise at this cadence, PERF.md).
    resources_interval_s: float = 10.0
    # One-shot OOM forensics floor: the first sample seeing any device's
    # HBM headroom below this fraction writes resource_dump_player{p}.json
    # (the nan_dump pattern — the attribution picture an OOM kill would
    # destroy). 0 disables the dump.
    resources_headroom_warn_frac: float = 0.05
    # XLA compilation telemetry sub-switch (telemetry/compile.py):
    # per-function compile counts + wall time, post-warm-up retrace
    # detection with the offending avals, and the stager's AOT coverage
    # report, nested under the record's resources block.
    compile_enabled: bool = True
    # Alert engine sub-switch (telemetry/alerts.py): the declarative rule
    # set evaluated per periodic record, emitting the record's 'alerts'
    # block + alerts_player{p}.jsonl. Requires resources_enabled (the
    # machine-side rules read the resources block; tools/sentinel.py
    # re-evaluates offline regardless).
    alerts_enabled: bool = True
    # Rolling-median window (records) for the drop/growth rules; a rule
    # arms only once its metric has been healthy for a full window.
    alerts_window: int = 8
    # env/learner throughput below this fraction of its rolling median
    # fires *_throughput_drop.
    alerts_throughput_drop_frac: float = 0.5
    # Max heartbeat age (seconds) before heartbeat_stale fires.
    alerts_heartbeat_age_s: float = 120.0
    # sample_age p50 above this multiple of its rolling median fires
    # staleness_growth.
    alerts_staleness_growth_factor: float = 4.0
    # Minimum per-device HBM headroom fraction before hbm_headroom fires.
    alerts_hbm_headroom_frac: float = 0.05
    # Post-warm-up retraces within one log interval at/above this count
    # fire retrace_storm.
    alerts_retrace_storm: int = 3
    # -- cost model & roofline (ISSUE 9) --
    # Kill switch for the periodic record's one-shot 'costs' block: the
    # analytic per-component (torso/lstm/head/sum-tree/replay) FLOPs +
    # bytes summary of the configured train step, attached by the
    # Learner at its first metrics flush (pure config math — no compile,
    # no device work). Off (or with the master `enabled` off) the record
    # schema is byte-identical to pre-PR9. The offline XLA cost tools
    # (`make costs` / `make roofline` / the `make regress` costs gate —
    # telemetry/costmodel.py, tools/roofline.py) are unaffected: they
    # run out-of-process against the config, not the live run.
    costmodel_enabled: bool = True
    # Sharded-anakin balance: max/min per-shard ingested env-steps over
    # the log interval (the record's anakin.shard_imbalance) at/above
    # this ratio fires shard_imbalance. Today's lockstep fused program
    # keeps the ratio at exactly 1.0 (full blocks on every shard every
    # segment) — the rule is the standing guard for compositions that
    # can skew it (ragged per-shard emission, elastic meshes), where a
    # lagging shard drags the whole lockstep program to its pace.
    alerts_shard_imbalance: float = 1.5
    # -- replay & data-pathology observability (ISSUE 10) --
    # Pillar kill switch for the replay diagnostics fused into the jitted
    # sample/update path (telemetry/replaydiag.py): sum-tree / priority
    # health (leaf histogram, effective sample size, collapse
    # indicators), per-slot sample-lifetime accounting (the
    # never-sampled-before-eviction fraction), and the per-ε-lane
    # composition of sampled batches. Off (or with the master `enabled`
    # off) the step factories compile WITHOUT the diagnostic state and
    # outputs, and the periodic record carries no 'replay_diag' block —
    # byte-identical to the PR9 schema (stability-tested).
    replay_diag_enabled: bool = True
    # Learner steps between sum-tree health snapshots (lax.cond inside
    # the fused step: the leaf-histogram scatter and eviction-counter
    # reads execute only on interval steps; the every-step residue is
    # one (B,)-scatter sample-count increment and a (lanes,)-bincount).
    replay_diag_interval: int = 50
    # Effective-sample-size fraction (ESS / active leaves) of the
    # sampling distribution below which priority_collapse fires: the
    # tree's mass has concentrated on this few of its live sequences.
    alerts_replay_ess_frac: float = 0.05
    # Fraction of live leaves sitting at the tree's max priority at/above
    # which priority_saturation fires (a mass of ties at max means
    # prioritization has stopped discriminating).
    alerts_priority_saturation: float = 0.5
    # never_sampled_frac above this multiple of its own rolling median
    # fires never_sampled_growth (replay sized/prioritized wrong: an
    # increasing share of experience is evicted unseen).
    alerts_never_sampled_growth: float = 2.0
    # Fraction of the global ε-ladder lanes contributing ZERO sequences
    # to the interval's sampled batches at/above which lane_starvation
    # fires.
    alerts_lane_starved_frac: float = 0.5
    # -- fleet observability (ISSUE 12; telemetry/fleet.py) --
    # Pillar kill switch for the multihost fleet plane: the lockstep
    # psum row widened with per-rank step-time gauges (sum/max/min +
    # one-hot straggler argmax + the all-gathered per-row tables),
    # per-iteration compute-vs-blocked lockstep timing, the rank-0
    # FleetAggregator's 'fleet' block on the periodic record, per-rank
    # AlertEngines on ranks > 0 (firings -> alerts_host{r}.jsonl), and
    # the clock-anchored host rows the cross-host trace merge aligns
    # on. False (or the master `enabled` off) compiles the exact PR-10
    # lockstep programs and leaves records and host rows byte-identical
    # to the PR-10 schema (stability-tested). Single-controller
    # (non-multihost) runs are unaffected either way.
    fleet_enabled: bool = True
    # Size cap (bytes) on each telemetry_host{r}.jsonl before it rotates
    # to telemetry_host{r}.jsonl.1 (one generation kept — a pod run
    # holds at most ~2x this per rank). 0 = unbounded (pre-PR12).
    fleet_host_row_max_bytes: int = 16 * 2**20
    # Max/min per-rank mean step time (the fleet block's
    # step_time.skew — the shard_imbalance convention) at/above which
    # rank_straggler fires; 1.0 = perfectly balanced.
    alerts_rank_straggler: float = 2.0
    # Fraction of loop time this rank spent blocked in the lockstep
    # collective (fleet.lockstep.wait_frac) at/above which
    # lockstep_wait_frac fires — the DCN barrier is eating step time.
    alerts_lockstep_wait_frac: float = 0.75
    # Max/min per-rank ingested env-steps over the interval
    # (fleet.env_steps.divergence; a zero-rank reads against a floor of
    # 1) at/above which fleet_desync fires.
    alerts_fleet_desync: float = 4.0
    # Stalest other-rank host-row age (seconds, fleet.host_rows.max_age_s
    # on rank 0) at/above which missing_rank fires — a rank stopped
    # writing its row (wedged or dead past the heartbeat horizon).
    alerts_missing_rank_age_s: float = 120.0
    # -- serving plane (ISSUE 13; the record's 'serving' block) --
    # Client-visible request-latency P99 (serving.latency.p99_ms —
    # includes queueing, retries, and timed-out attempts) at/above which
    # serve_latency_slo fires: the SLO ceiling. Inactive on records
    # without a serving block (every non-served run).
    alerts_serve_p99_ms: float = 1000.0
    # Fraction of the interval's dispatched batches that went out with
    # fill == 1 while >1 clients were connected (serving.batch.
    # starved_frac) at/above which serve_batch_starvation fires — the
    # micro-batcher is not coalescing despite load (deadline too tight,
    # or clients serialized behind something).
    alerts_serve_starved_frac: float = 0.95
    # Cumulative client disconnects (serving.clients.disconnects)
    # growing by at least this much within one interval fires
    # serve_client_churn (counter semantics — one burst, one alert).
    alerts_serve_churn: float = 3.0
    # Interval shed fraction (serving.admission.shed_frac — requests
    # rejected at the queue-depth bound over shed+replied) at/above
    # which serve_brownout fires: the fleet is actively shedding load to
    # hold the latency SLO — capacity is the problem, not the server.
    # Inactive when admission control is off (no admission sub-block).
    alerts_serve_shed_frac: float = 0.2
    # -- quantized inference plane (ISSUE 14; the record's 'quant' block) --
    # Forward calls between accuracy probes when network.inference_dtype
    # != "f32": every probe_interval-th acting forward also runs the f32
    # twin on the SAME live batch (a lax.cond inside the jitted forward —
    # steady-state cost amortizes to ~nothing) and feeds max |Q_f32 −
    # Q_quant| + the greedy-action agreement fraction into the periodic
    # record's 'quant' block. 0 disables probing (the block still carries
    # the active dtype). The anakin path probes once per acting segment
    # (already ~1/block_length of the scan's cost).
    quant_probe_interval: int = 256
    # Interval greedy-action agreement fraction (quant.agree_frac, the
    # lane-weighted mean over the interval's probes) at/below which
    # quant_divergence fires — the quantized policy is no longer acting
    # like its f32 twin. Inactive on records without a quant block
    # (every inference_dtype="f32" run).
    alerts_quant_agreement: float = 0.95
    # -- elastic fleet / replay service (ISSUE 15; the record's
    # 'replay_service' block, r2d2_tpu/fleet/) --
    # Interval spill-tier eviction/demotion ratio
    # (replay_service.spill.thrash_frac) at/above which spill_thrash
    # fires: demoted pages are falling off the LRU end before ever
    # being re-promoted — the device ring is turning over faster than
    # the spill tier can cycle experience back, so the tier is a pure
    # write-through loss (grow spill_blocks or slow collection).
    alerts_spill_thrash_frac: float = 0.5
    # Max fan-out relay lag in publications
    # (replay_service.fanout.max_lag: root publish count minus the
    # slowest relay's adopted count) at/above which fanout_lag fires —
    # a tier of the weight tree has stopped propagating and its
    # subtree's actors act on stale params.
    alerts_fanout_lag: float = 8.0
    # Leased-but-silent slot count (replay_service.membership.orphaned:
    # ACTIVE slots whose heartbeat is stale past the orphan horizon) at/
    # above which orphaned_slot fires — a worker vanished without its
    # lease being parked or re-adopted.
    alerts_orphaned_slots: float = 1.0
    # Service ingest backlog (replay_service.ingest.backlog: blocks
    # queued behind the service's grouped commit at the last drain) at/
    # above which ingest_backlog fires — producers are bursting faster
    # than the service's dispatch plane drains, so blocks age in the
    # queue before ever becoming samplable (raise
    # fleet.ingest_batch_blocks or slow collection).
    alerts_ingest_backlog: float = 64.0
    # -- crash-recovery plane (ISSUE 18; the record's 'recovery' block) --
    # Age (seconds) of the newest durable replay snapshot
    # (recovery.snapshot.age_s) at/above which snapshot_stale fires —
    # the writer has stopped committing cuts, so a crash now loses more
    # than one runtime.snapshot_interval of experience. Inactive on
    # records without a recovery block (snapshot_interval = 0).
    alerts_snapshot_stale_s: float = 600.0
    # Supervisor relaunches of the learner (recovery.supervisor.restarts,
    # cumulative within the supervised run) at/above which recovery_loop
    # fires — the learner is crash-looping through auto-resume instead
    # of making progress (the breaker parks it one rung later).
    alerts_recovery_loop: float = 2.0
    # -- cross-plane distributed tracing (ISSUE 19; telemetry/tracing.py) --
    # Kill switch for causal trace propagation on BOTH data paths:
    # serving requests carry a trace dict (per-hop wall stamps client ->
    # router -> server micro-batch -> reply; two gated fields on the shm
    # request layout) and every Nth experience block carries the
    # Block.trace_ms lineage stamp from emission through ingest / spill /
    # sample to train consumption — the record's 'trace' block with the
    # end-to-end env-step->gradient latency histogram. Default OFF: the
    # stamp is a trailing pytree leaf and two wire fields, and the
    # kill-switch contract (records, wire frames, and block schemas
    # byte-identical when off) means an opt-in plane, like
    # snapshot_interval and spill_prefetch before it.
    tracing_enabled: bool = False
    # Every Nth emitted block gets a lineage stamp / every Nth serve
    # exchange gets a trace dict (1 = trace everything; the benched <= 2%
    # overhead budget holds at the default).
    trace_sample_every: int = 16
    # Control-tower collector sub-switch (telemetry/tower.py +
    # tools/tower.py): gates the process-identity header + clock anchor
    # on the serve-fleet / ReplayService periodic rows the tower join
    # and the cross-process Perfetto merge align on. Pull-based (the
    # tower tails files) — on by default; rows gain only the '_proc'
    # header key.
    tower_enabled: bool = True
    # -- per-tier replay telemetry (ISSUE 19 satellite; ROADMAP 4d) --
    # Adds promotion-latency + bytes-per-tier sub-blocks to the record's
    # replay_service.spill block. Off => the block is byte-identical to
    # the PR-18 schema.
    replay_tiers_enabled: bool = False
    # Spill promotion latency p95 (replay_service.spill.
    # promotion_latency.p95_ms — time-in-tier of pages promoted this
    # interval) at/above which spill_promotion_latency fires: demoted
    # experience is sitting so long in the host tier that it returns
    # stale (grow promote_per_sample / spill_prefetch, or shrink the
    # tier).
    alerts_spill_promotion_ms: float = 60_000.0
    # Tower alert rule: e2e_experience_latency p50 (the record trace
    # block's env-step->gradient latency) above this multiple of its own
    # rolling median fires e2e_latency_growth — experience is aging
    # somewhere between emission and the gradient.
    alerts_e2e_latency_growth: float = 4.0
    # -- policy-quality pillar (ISSUE 20; telemetry/quality.py) --
    # Master switch: continuous eval + Q-calibration + the record's
    # 'quality' block + the quality_player{p}.jsonl ledger stream. Off
    # (default) => nothing is constructed and records are byte-identical
    # to the PR-19 schema (the kill-switch contract).
    quality_enabled: bool = False
    # Background evaluator cadence / work: seconds between checkpoint
    # polls, eval episodes per scenario, served eval clients (the eval
    # rollouts ride cli/evaluate's --serve machinery when serving is on).
    quality_eval_interval_s: float = 60.0
    quality_eval_rounds: int = 2
    quality_eval_clients: int = 2
    # Every Nth finished actor block feeds the Q-calibration join
    # (1 = every block; the tap is one convolution per 400-step block).
    quality_calib_sample_every: int = 1
    # quality_regression: eval mean_return dropping below this fraction
    # of its own rolling median fires (drop rule — return scales are
    # env-relative, so the rule is too).
    alerts_quality_regression: float = 0.5
    # canary_divergence: shadow greedy-disagreement fraction at/above
    # this fires (crit — the candidate disagrees with live on mirrored
    # traffic beyond the promotion gate's own bound).
    alerts_canary_divergence: float = 0.25
    # promotion_stall: a canary staged longer than this many seconds
    # without a promote/refuse/rollback verdict fires.
    alerts_promotion_stall_s: float = 600.0


@dataclass(frozen=True)
class RuntimeConfig:
    """Process orchestration, logging, checkpointing (ref config.py:8-10,20-21,40)."""

    save_dir: str = "models"
    pretrain: str = ""               # warm-start checkpoint path ("" = none)
    # Full-resume checkpoint path: restores params, target_params, opt_state,
    # step, and env_steps into the learner (the reference can only warm-start
    # weights, worker.py:260-261; SURVEY §5.4 sets the full-state bar).
    resume: str = ""
    save_interval: int = 1_000       # learner steps between checkpoints
    log_interval: float = 20.0       # seconds between metric log lines
    weight_publish_interval: int = 2  # learner steps between weight publications
    # Fused train steps per device dispatch (lax.scan). >1 amortizes host
    # dispatch latency; weight publish / checkpoint cadence coarsens to
    # dispatch boundaries. 1 = reference-faithful per-step cadence.
    # -1 = auto: 16 on TPU (the measured winner of the round-3 bench matrix,
    # +28% over per-step dispatch on v5e; identical math — same RNG chain
    # and target-sync schedule), 1 elsewhere (the XLA:CPU lowering of the
    # scanned step runs ~12x slower per step than the unrolled jit —
    # measured round 3, PERF.md). Publishes still land every
    # ceil(interval/k)*k steps, far fresher than the reference actors'
    # 400-step pull cadence (worker.py:568).
    steps_per_dispatch: int = -1

    def resolved_steps_per_dispatch(self) -> int:
        if self.steps_per_dispatch > 0:
            return self.steps_per_dispatch
        import jax
        return 16 if jax.default_backend() == "tpu" else 1
    prefetch_batches: int = 4        # learner-side batch prefetch depth (ref worker.py:302)
    # Process-mode experience transport: native shared-memory MPMC ring
    # (one memcpy per side — the plasma-store equivalent, shm_feeder.py);
    # falls back to mp.Queue (pickle through a pipe) if the C++ toolchain
    # is unavailable or the flag is off.
    shm_transport: bool = True
    test_epsilon: float = 0.01
    seed: int = 0
    profile_dir: str = ""            # non-empty: write jax.profiler traces here
    # Mid-run xprof trigger: > 0 arms a ONE-SHOT jax.profiler capture that
    # starts when the learner step counter first reaches this value and
    # runs for min(log_interval, 30)s — profiling the steady state instead
    # of (or in addition to) the first-interval capture profile_dir
    # enables. Traces land in profile_dir, or {save_dir}/xprof when
    # profile_dir is unset. SIGUSR2 triggers the same capture on demand.
    profile_at_step: int = 0
    restart_dead_actors: bool = True  # supervisor (the reference has none, SURVEY §5.3)
    # -- worker health (heartbeats / watchdog / backoff / breaker) --
    # Seconds between supervision passes (dead-worker scan, hang watchdog,
    # ring reclamation, stall detector) — decoupled from log_interval so
    # hang detection latency does not ride the logging cadence.
    supervise_interval_s: float = 5.0
    # Hang watchdog: a worker that is alive but whose heartbeat (published
    # per block emit, and while parked under feeder back-pressure) is older
    # than this is killed (process) or flagged+abandoned (thread) and
    # routed through the normal respawn path. 0 disables hang detection.
    hang_timeout_s: float = 120.0
    # Grace before a worker's FIRST heartbeat (process spawn + jax import +
    # env construction + first block can far exceed hang_timeout_s); a
    # worker wedged during bring-up — the classic stuck ViZDoom multiplayer
    # join — is still detected, just on this slower clock.
    hang_spawn_grace_s: float = 300.0
    # Per-slot exponential restart backoff: the first respawn is
    # immediate; each further failure inside restart_window_s doubles the
    # wait, starting at base for the second (k-th failure waits
    # base * 2^(k-2), capped at max). Stops a crash-looping actor from
    # burning a CPU respawning every supervision tick.
    restart_backoff_base_s: float = 1.0
    restart_backoff_max_s: float = 60.0
    # Crash-loop circuit breaker: after this many failures inside
    # restart_window_s the slot is PARKED (no further respawns; training
    # continues degraded; surfaced in metrics as actor_parked_slots /
    # actor_breaker_trips). 0 disables the breaker.
    max_restarts_per_window: int = 5
    restart_window_s: float = 300.0
    # Learner-side stall detector: when ingestion sits at zero new blocks
    # for this long while workers are nominally alive and the rate limiter
    # is not deliberately pausing, emit a one-shot diagnostic dump
    # (per-slot heartbeat ages, queue/ring occupancy, limiter state)
    # instead of starving silently. 0 disables.
    ingest_stall_timeout_s: float = 300.0
    # -- crash-recovery plane (ISSUE 18) --
    # Learner steps between durable replay snapshots: at each interval
    # boundary the learner captures a consistent cut of the replay plane
    # (every shard's ReplayState + ring accounting + spill pages + rr
    # cursors) at the commit boundary between train dispatches, and a
    # background writer serializes it to {save_dir}/replay_player{p}.npz
    # with an atomic tmp+rename manifest (replay/snapshot.py). 0 = off
    # (no snapshot files, no 'recovery' record block — records stay
    # byte-identical to the pre-PR18 schema).
    snapshot_interval: int = 0
    # Restore replay contents on resume: when runtime.resume is set and a
    # replay snapshot manifest exists next to the checkpoint, the learner
    # reloads every shard's ring/tree/stamps/spill bit-exactly before
    # training continues. Off restores params/opt-state only (the
    # pre-PR18 resume).
    restore_replay: bool = True
    # Supervisor rung (runtime/supervisor.py, wired in cli/train.py): run
    # training in a supervised child process; on learner death (or
    # SIGKILL preemption of the child) the supervisor relaunches it with
    # runtime.resume pointed at the newest checkpoint + replay snapshot.
    # The relaunch ladder reuses the PR-3 worker-health knobs above
    # (restart_backoff_*, max_restarts_per_window, restart_window_s) as
    # the crash-loop breaker.
    auto_resume: bool = False
    # Checkpoint retention: keep only the newest K checkpoint dirs per
    # player (plus their .config.json sidecars and any per-checkpoint
    # snapshot sets) after each save — disk growth was unbounded before.
    # 0 = keep everything.
    keep_checkpoints: int = 0


@dataclass(frozen=True)
class Config:
    """Root config. Construction validates cross-section size invariants the
    replay layout depends on (block/sequence divisibility), so a bad genetic-
    search sample fails here rather than corrupting buffer indexing later."""

    env: EnvConfig = field(default_factory=EnvConfig)
    network: NetworkConfig = field(default_factory=NetworkConfig)
    sequence: SequenceConfig = field(default_factory=SequenceConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    actor: ActorConfig = field(default_factory=ActorConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    multiplayer: MultiplayerConfig = field(default_factory=MultiplayerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)

    def __post_init__(self):
        if self.replay.block_length % self.sequence.learning_steps != 0:
            raise ValueError(
                f"replay.block_length ({self.replay.block_length}) must be a "
                f"multiple of sequence.learning_steps ({self.sequence.learning_steps})"
            )
        if self.replay.capacity % self.replay.block_length != 0:
            raise ValueError(
                f"replay.capacity ({self.replay.capacity}) must be a multiple "
                f"of replay.block_length ({self.replay.block_length})"
            )
        if self.sequence.forward_steps < 1:
            raise ValueError("sequence.forward_steps must be >= 1")
        if self.replay.ingest_batch_blocks == 0 or \
                self.replay.ingest_batch_blocks < -1:
            raise ValueError(
                f"replay.ingest_batch_blocks ({self.replay.ingest_batch_blocks})"
                " must be -1 (auto) or >= 1")
        if self.replay.ingest_batch_blocks > self.num_blocks:
            raise ValueError(
                f"replay.ingest_batch_blocks ({self.replay.ingest_batch_blocks})"
                f" must be <= num_blocks ({self.num_blocks}): replay_add_many"
                " scatter rows would alias in the ring")
        if self.replay.drain_max_blocks < 1:
            raise ValueError(
                f"replay.drain_max_blocks ({self.replay.drain_max_blocks}) "
                "must be >= 1")
        if self.actor.envs_per_actor < 1:
            raise ValueError(
                f"actor.envs_per_actor ({self.actor.envs_per_actor}) must be "
                ">= 1")
        if self.actor.envs_per_actor > 100:
            raise ValueError(
                f"actor.envs_per_actor ({self.actor.envs_per_actor}) must be "
                "<= 100: per-lane seeds fill the worker's 100-wide seed "
                "window (runtime.seed + 100*actor_idx + lane); more lanes "
                "would duplicate the next worker's env/RNG streams — scale "
                "actor.num_actors instead")
        if self.env.episode_len < 1:
            raise ValueError(
                f"env.episode_len ({self.env.episode_len}) must be >= 1")
        if self.env.grid_size < 2:
            raise ValueError(
                f"env.grid_size ({self.env.grid_size}) must be >= 2")
        if self.env.grid_size > min(self.env.frame_height,
                                    self.env.frame_width):
            raise ValueError(
                f"env.grid_size ({self.env.grid_size}) must be <= the frame "
                f"size ({self.env.frame_height}x{self.env.frame_width}): a "
                "grid cell needs at least one pixel, or the gridworld "
                "renders a uniform background (zero-information obs)")
        if self.actor.anakin_lanes < 1:
            raise ValueError(
                f"actor.anakin_lanes ({self.actor.anakin_lanes}) must be "
                ">= 1")
        if self.actor.anakin_scans_per_train < 1:
            raise ValueError(
                f"actor.anakin_scans_per_train "
                f"({self.actor.anakin_scans_per_train}) must be >= 1")
        if isinstance(self.actor.anakin_priority, str):
            if self.actor.anakin_priority != "td":
                raise ValueError(
                    f"actor.anakin_priority ({self.actor.anakin_priority!r})"
                    " must be 'td' (in-graph n-step TD seeding from the "
                    "acting policy's Q-values) or a positive constant stamp")
        elif self.actor.anakin_priority <= 0:
            raise ValueError(
                f"actor.anakin_priority ({self.actor.anakin_priority}) must "
                "be > 0: zero-priority sequences are unsamplable, so a "
                "freshly emitted block could never be trained on")
        if self.actor.on_device:
            # the fused acting path's structural preconditions fail HERE,
            # at config construction, with the fix spelled out — not as an
            # opaque shape error inside the jitted scan
            if self.replay.placement != "device":
                raise ValueError(
                    "actor.on_device requires replay.placement='device': "
                    "the acting scan ring-writes blocks straight into the "
                    "HBM-resident replay (host placement would re-introduce "
                    "the host round-trip the path exists to remove)")
            if self.env.episode_len % self.replay.block_length != 0:
                raise ValueError(
                    f"actor.on_device requires env.episode_len "
                    f"({self.env.episode_len}) to be a multiple of "
                    f"replay.block_length ({self.replay.block_length}): the "
                    "fused scan emits fixed block_length-step blocks, so "
                    "episode ends must land on block boundaries (the host "
                    "path's emit-on-done semantics)")
            if self.mesh.mp > 1:
                raise ValueError(
                    "actor.on_device composes with data-parallel meshes "
                    "only: the fused acting scan runs per-shard lane "
                    "groups over mesh.dp, but model parallelism (mesh.mp "
                    f"= {self.mesh.mp}) shards the network's feature dims "
                    "through the GSPMD learner step, which the acting "
                    "scan does not run under — set mesh.mp=1 (mesh.dp > 1 "
                    "is fine) or actor.on_device=false")
            if self.mesh.dp > 1 and \
                    self.actor.anakin_lanes % self.mesh.dp != 0:
                # the lane/shard divisibility contract, enforced HERE so
                # a bad pairing fails at config construction, not as a
                # reshape error inside the traced shard_map program
                raise ValueError(
                    f"actor.anakin_lanes ({self.actor.anakin_lanes}) must "
                    f"be divisible by mesh.dp ({self.mesh.dp}): the fused "
                    "acting scan partitions the lanes into equal "
                    "per-shard groups (anakin_lanes % dp == 0) — adjust "
                    "actor.anakin_lanes or mesh.dp")
            # mesh.dp=-1 (all devices) resolves at runtime; the loop
            # re-checks both contracts against the resolved dp there
            per_shard = (self.actor.anakin_lanes // self.mesh.dp
                         if self.mesh.dp > 1 else self.actor.anakin_lanes)
            if self.mesh.dp >= 1 and per_shard > self.num_blocks:
                raise ValueError(
                    f"actor.anakin_lanes ({self.actor.anakin_lanes}) must "
                    f"leave each shard's lane group ({per_shard}) <= "
                    f"num_blocks ({self.num_blocks}): each segment "
                    "ring-writes one block per lane in a single "
                    "replay_add_many dispatch, whose scatter rows must not "
                    "alias — grow replay.capacity or lower the lane count")
            if self.multiplayer.enabled:
                raise ValueError(
                    "actor.on_device is not supported with multiplayer "
                    "(the jitted envs have no host/join engine wiring)")
            if self.mesh.multihost:
                raise ValueError(
                    "actor.on_device is single-controller only (the fused "
                    "loop is not integrated with the lockstep multihost "
                    "trainer yet) — unset mesh.multihost")
            if self.actor.fault_spec:
                raise ValueError(
                    "actor.fault_spec requires the host actor fleet: fault "
                    "injection lives at the worker block sink "
                    "(runtime/actor_loop.py), which the fused on-device "
                    "loop never runs — a chaos run with actor.on_device "
                    "would inject nothing and report vacuously healthy")
        if self.actor.fault_spec:
            from r2d2_tpu.tools.chaos import (parse_fault_spec,
                                              parse_join_spec)
            faults = parse_fault_spec(self.actor.fault_spec)
            joins = parse_join_spec(self.actor.fault_spec)
            # membership faults may target spare slots (joiners lease
            # them), so the bound is the elastic fleet's MAX width
            width = self.fleet.resolved_max_slots(self.actor.num_actors)
            bad = sorted(s for s in set(faults) | set(joins) if s >= width)
            if bad:
                raise ValueError(
                    f"actor.fault_spec targets slot(s) {bad} outside the "
                    f"fleet of {width} slot(s) (actor.num_actors workers "
                    "+ fleet.max_slots spares)")
            membership_kinds = sorted(
                s for s, f in faults.items() if f.kind == "leave")
            if (joins or membership_kinds) and not self.fleet.elastic:
                raise ValueError(
                    "actor.fault_spec 'join'/'leave' entries require "
                    "fleet.elastic=true: they are MEMBERSHIP faults — a "
                    "leave parks the slot for re-adoption and a join "
                    "adopts it, semantics the frozen fleet's "
                    "respawn-in-place supervision does not have (a "
                    "non-elastic leave would just crash-loop the "
                    "worker)")
            if self.actor.inference != "server":
                disc = [s for s, f in faults.items()
                        if f.kind == "disconnect"]
                if disc:
                    raise ValueError(
                        f"actor.fault_spec slot(s) {disc} use the "
                        "'disconnect' kind, which injects at the serve "
                        "client — it requires actor.inference='server' "
                        "(with local inference there is no connection to "
                        "drop, so the run would report vacuously healthy)")
        if self.actor.inference not in ("local", "server"):
            raise ValueError(
                f"actor.inference ({self.actor.inference!r}) must be "
                "'local' or 'server'")
        if self.actor.inference == "server":
            if self.actor.on_device:
                raise ValueError(
                    "actor.inference='server' requires the host actor "
                    "fleet: the fused on-device loop (actor.on_device) "
                    "has no per-step policy client — its acting forward "
                    "is already device-resident")
            if self.mesh.multihost:
                raise ValueError(
                    "actor.inference='server' is single-host for now: the "
                    "multihost lockstep fleet wires its own weight "
                    "distribution — route its actors through a serve "
                    "transport in the elastic-fleet arc (ROADMAP item 4)")
            lanes = self.actor.num_actors * self.actor.envs_per_actor
            if lanes > self.serve.state_slots:
                raise ValueError(
                    f"actor fleet has {lanes} lanes but serve.state_slots "
                    f"is {self.serve.state_slots}: every lane leases a "
                    "server-side state slot, so an undersized cache would "
                    "thrash (evict live episodes) — raise "
                    "serve.state_slots")
        if self.serve.max_batch < 1:
            raise ValueError(
                f"serve.max_batch ({self.serve.max_batch}) must be >= 1")
        if self.serve.deadline_ms < 0:
            raise ValueError(
                f"serve.deadline_ms ({self.serve.deadline_ms}) must be "
                ">= 0")
        if self.serve.state_slots < 1 or self.serve.state_shards < 1:
            raise ValueError(
                "serve.state_slots and serve.state_shards must be >= 1")
        if self.serve.state_slots % self.serve.state_shards != 0:
            raise ValueError(
                f"serve.state_slots ({self.serve.state_slots}) must be "
                f"divisible by serve.state_shards "
                f"({self.serve.state_shards}): shards are equal slot "
                "groups")
        for fname in ("lease_timeout_s", "request_timeout_s",
                      "max_retry_s", "weight_poll_interval_s"):
            if getattr(self.serve, fname) <= 0:
                raise ValueError(f"serve.{fname} must be > 0")
        if self.serve.request_ttl_s < 0:
            raise ValueError(
                f"serve.request_ttl_s ({self.serve.request_ttl_s}) must "
                "be >= 0 (0 disables expiry)")
        if self.serve.transport not in ("auto", "shm", "socket"):
            raise ValueError(
                f"serve.transport ({self.serve.transport!r}) must be "
                "'auto', 'shm', or 'socket'")
        if self.serve.request_ring_slots < 2 or \
                self.serve.reply_ring_slots < 2:
            raise ValueError(
                "serve.request_ring_slots and serve.reply_ring_slots "
                "must be >= 2")
        if self.telemetry.alerts_serve_p99_ms <= 0:
            raise ValueError(
                f"telemetry.alerts_serve_p99_ms "
                f"({self.telemetry.alerts_serve_p99_ms}) must be > 0")
        if not 0 < self.telemetry.alerts_serve_starved_frac <= 1:
            raise ValueError(
                f"telemetry.alerts_serve_starved_frac "
                f"({self.telemetry.alerts_serve_starved_frac}) must be in "
                "(0, 1]")
        if self.telemetry.alerts_serve_churn < 1:
            raise ValueError(
                f"telemetry.alerts_serve_churn "
                f"({self.telemetry.alerts_serve_churn}) must be >= 1")
        # -- serving fleet (ISSUE 17): the router partitions whole
        # client-hash shard groups, so the server count is bounded by
        # the shard count and shm (single-ring) cannot host N loops --
        if self.serve.servers < 1:
            raise ValueError(
                f"serve.servers ({self.serve.servers}) must be >= 1")
        if self.serve.servers > self.serve.state_shards:
            raise ValueError(
                f"serve.servers ({self.serve.servers}) must be <= "
                f"serve.state_shards ({self.serve.state_shards}): each "
                "server owns at least one whole client-hash shard group "
                "— raise state_shards or lower servers")
        if self.serve.max_servers != 0 and not (
                self.serve.servers <= self.serve.max_servers
                <= self.serve.state_shards):
            raise ValueError(
                f"serve.max_servers ({self.serve.max_servers}) must be 0 "
                f"(= serve.servers) or in [serve.servers, "
                f"serve.state_shards] — it is the elastic fleet's slot "
                "board width and every server needs >= 1 shard")
        if self.serve.queue_depth_bound < 0:
            raise ValueError(
                f"serve.queue_depth_bound ({self.serve.queue_depth_bound})"
                " must be >= 0 (0 disables admission control)")
        if self.serve.servers > 1 and self.serve.transport == "shm":
            raise ValueError(
                "serve.servers > 1 requires transport 'auto' or "
                "'socket': the shm rung is a single request ring with "
                "one server-side consumer — multi-server routing rides "
                "per-server sockets (process mode) or in-proc endpoints "
                "(thread mode)")
        if not 0 < self.telemetry.alerts_serve_shed_frac <= 1:
            raise ValueError(
                f"telemetry.alerts_serve_shed_frac "
                f"({self.telemetry.alerts_serve_shed_frac}) must be in "
                "(0, 1]")
        if self.fleet.lease_transport not in ("", "socket"):
            raise ValueError(
                f"fleet.lease_transport ({self.fleet.lease_transport!r}) "
                "must be '' (in-proc only) or 'socket' (serve the lease "
                "API for cli/join.py)")
        if self.fleet.lease_port < 0:
            raise ValueError(
                f"fleet.lease_port ({self.fleet.lease_port}) must be "
                ">= 0 (0 = ephemeral)")
        # -- elastic fleet (ISSUE 15): structural preconditions fail at
        # config construction with the fix spelled out --
        fl = self.fleet
        if fl.replay_shards < 0:
            raise ValueError(
                f"fleet.replay_shards ({fl.replay_shards}) must be >= 0 "
                "(0 = legacy in-mesh replay)")
        if fl.replay_shards > 0:
            if self.replay.placement != "device":
                raise ValueError(
                    "fleet.replay_shards requires replay.placement="
                    "'device': the service's shards are the jitted "
                    "HBM-resident rings (host placement already has its "
                    "own CPU tree — disaggregate the device plane)")
            if self.mesh.dp != 1 or self.mesh.mp != 1:
                raise ValueError(
                    "fleet.replay_shards composes with a 1x1 mesh only: "
                    "the service IS the replay sharding layer (it "
                    "generalizes the dp-sharded rings into addressable "
                    "shards) — set mesh.dp=1/mesh.mp=1 or use the "
                    "in-mesh dp sharding without the service")
            if self.actor.on_device:
                raise ValueError(
                    "fleet.replay_shards requires the host actor fleet: "
                    "the fused on-device loop ring-writes straight into "
                    "its colocated replay (actor.on_device) — the "
                    "service exists for producers that do NOT share the "
                    "learner's program")
            if self.mesh.multihost:
                raise ValueError(
                    "fleet.replay_shards is single-controller for now — "
                    "the lockstep multihost trainer keeps its per-rank "
                    "in-mesh shards (routing its ranks through the "
                    "service is the ROADMAP item-1 composition)")
            if self.num_blocks % fl.replay_shards != 0:
                raise ValueError(
                    f"fleet.replay_shards ({fl.replay_shards}) must "
                    f"divide num_blocks ({self.num_blocks}): shards are "
                    "equal device-ring slices — adjust replay.capacity "
                    "or the shard count")
            if fl.replay_route == "lane":
                # lanes are contiguous [0, max_slots * envs_per_actor):
                # residues mod replay_shards cover every shard iff there
                # are at least as many lanes as shards — otherwise some
                # shard can never receive a block and the per-shard
                # training gate stays closed FOREVER (errorless stall)
                lanes = (fl.resolved_max_slots(self.actor.num_actors)
                         * self.actor.envs_per_actor)
                if lanes < fl.replay_shards:
                    raise ValueError(
                        f"fleet.replay_route='lane' with "
                        f"{fl.replay_shards} shards needs at least that "
                        f"many ε-ladder lanes (fleet has {lanes}): shard "
                        "s only receives lanes with lane % shards == s, "
                        "so an uncovered shard would hold the training "
                        "gate closed forever — grow the fleet or use "
                        "replay_route='round_robin'")
        if fl.spill_blocks < 0:
            raise ValueError(
                f"fleet.spill_blocks ({fl.spill_blocks}) must be >= 0")
        if fl.spill_blocks > 0 and fl.replay_shards < 1:
            raise ValueError(
                "fleet.spill_blocks requires fleet.replay_shards >= 1: "
                "the spill tier is the replay service's demotion target "
                "(the in-mesh rings overwrite in place)")
        if fl.spill_promote_per_sample < 0:
            raise ValueError(
                f"fleet.spill_promote_per_sample "
                f"({fl.spill_promote_per_sample}) must be >= 0")
        if fl.replay_route not in ("round_robin", "lane"):
            raise ValueError(
                f"fleet.replay_route ({fl.replay_route!r}) must be "
                "'round_robin' or 'lane'")
        if fl.service_transport not in ("", "socket"):
            raise ValueError(
                f"fleet.service_transport ({fl.service_transport!r}) "
                "must be '' (in-proc producers only) or 'socket'")
        if fl.service_transport and fl.replay_shards < 1:
            raise ValueError(
                "fleet.service_transport requires fleet.replay_shards "
                ">= 1 (there is no service to listen for)")
        # -- batched/pipelined service data plane (ISSUE 16) --
        if fl.ingest_batch_blocks < 1:
            raise ValueError(
                f"fleet.ingest_batch_blocks ({fl.ingest_batch_blocks}) "
                "must be >= 1 (1 = the per-block replay_add path)")
        if fl.ingest_batch_blocks > 1 and fl.replay_shards < 1:
            raise ValueError(
                "fleet.ingest_batch_blocks > 1 requires "
                "fleet.replay_shards >= 1: grouped ingest is the "
                "service's commit plane (the in-mesh path already has "
                "replay.ingest_batch_blocks) — a run without the "
                "service would silently ignore the knob")
        if fl.socket_window < 1:
            raise ValueError(
                f"fleet.socket_window ({fl.socket_window}) must be >= 1 "
                "(1 = one-frame-one-ack lockstep)")
        if fl.socket_window > 1 and fl.service_transport != "socket":
            raise ValueError(
                "fleet.socket_window > 1 requires "
                "fleet.service_transport='socket': the in-flight window "
                "is the socket rung's ack pipeline — in-proc producers "
                "have no frames to window")
        if fl.spill_prefetch and fl.spill_blocks < 1:
            raise ValueError(
                "fleet.spill_prefetch requires fleet.spill_blocks >= 1: "
                "priority-aware prefetch promotes from the spill tier — "
                "with no tier the knob would be silently ignored")
        if fl.sample_staging and fl.replay_shards < 1:
            raise ValueError(
                "fleet.sample_staging requires fleet.replay_shards >= 1:"
                " the stager pipelines the SERVICE sample path (the "
                "in-mesh learner already pipelines via the PR-2 ingest "
                "stager)")
        if fl.fanout_degree < 0 or fl.fanout_degree == 1:
            raise ValueError(
                f"fleet.fanout_degree ({fl.fanout_degree}) must be 0 "
                "(direct polling) or >= 2 (relay tree degree)")
        if fl.fanout_pull_interval_s < 0:
            raise ValueError(
                f"fleet.fanout_pull_interval_s "
                f"({fl.fanout_pull_interval_s}) must be >= 0")
        if fl.max_slots < 0:
            raise ValueError(
                f"fleet.max_slots ({fl.max_slots}) must be >= 0 "
                "(0 = actor.num_actors, no spares)")
        if 0 < fl.max_slots < self.actor.num_actors:
            raise ValueError(
                f"fleet.max_slots ({fl.max_slots}) must be >= "
                f"actor.num_actors ({self.actor.num_actors}): the "
                "startup fleet occupies the first num_actors slots")
        if self.actor.on_device and (fl.fanout_degree > 0 or fl.elastic
                                     or fl.max_slots > 0):
            raise ValueError(
                "fleet fan-out / elastic membership require the host "
                "actor fleet: the fused on-device loop (actor.on_device) "
                "has no weight service and no worker slots to lease")
        if self.mesh.multihost and (fl.elastic or fl.max_slots > 0):
            raise ValueError(
                "fleet.elastic / fleet.max_slots are single-controller "
                "for now: the lockstep multihost trainer's per-rank "
                "fleets have no membership plane (its supervision "
                "respawns in place) — a multihost run would silently "
                "ignore the knobs, so they are rejected instead "
                "(ROADMAP item 4 names the composition)")
        if not 0 < self.telemetry.alerts_spill_thrash_frac <= 1:
            raise ValueError(
                f"telemetry.alerts_spill_thrash_frac "
                f"({self.telemetry.alerts_spill_thrash_frac}) must be "
                "in (0, 1]")
        if self.telemetry.alerts_fanout_lag < 1:
            raise ValueError(
                f"telemetry.alerts_fanout_lag "
                f"({self.telemetry.alerts_fanout_lag}) must be >= 1 "
                "(publications behind the root)")
        if self.telemetry.alerts_orphaned_slots < 1:
            raise ValueError(
                f"telemetry.alerts_orphaned_slots "
                f"({self.telemetry.alerts_orphaned_slots}) must be >= 1")
        if self.telemetry.alerts_ingest_backlog < 1:
            raise ValueError(
                f"telemetry.alerts_ingest_backlog "
                f"({self.telemetry.alerts_ingest_backlog}) must be >= 1 "
                "(blocks queued behind the service drain)")
        if self.network.inference_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"network.inference_dtype "
                f"({self.network.inference_dtype!r}) must be 'f32', "
                "'bf16', or 'int8' — the acting/serving forward's weight "
                "dtype (the learner always trains in the network.bf16 "
                "policy regardless)")
        if self.telemetry.quant_probe_interval < 0:
            raise ValueError(
                f"telemetry.quant_probe_interval "
                f"({self.telemetry.quant_probe_interval}) must be >= 0 "
                "(0 disables the in-graph accuracy probe)")
        if not 0 < self.telemetry.alerts_quant_agreement <= 1:
            raise ValueError(
                f"telemetry.alerts_quant_agreement "
                f"({self.telemetry.alerts_quant_agreement}) must be in "
                "(0, 1]")
        for fname, lo in (("supervise_interval_s", 0.0),
                          ("restart_window_s", 0.0)):
            if getattr(self.runtime, fname) <= lo:
                raise ValueError(f"runtime.{fname} must be > {lo}")
        for fname in ("hang_timeout_s", "hang_spawn_grace_s",
                      "restart_backoff_base_s", "restart_backoff_max_s",
                      "ingest_stall_timeout_s"):
            if getattr(self.runtime, fname) < 0:
                raise ValueError(f"runtime.{fname} must be >= 0")
        if self.runtime.max_restarts_per_window < 0:
            raise ValueError("runtime.max_restarts_per_window must be >= 0")
        if self.runtime.profile_at_step < 0:
            raise ValueError("runtime.profile_at_step must be >= 0")
        if self.runtime.snapshot_interval < 0:
            raise ValueError(
                f"runtime.snapshot_interval "
                f"({self.runtime.snapshot_interval}) must be >= 0 "
                "(learner steps between replay snapshots; 0 disables)")
        if self.runtime.keep_checkpoints < 0:
            raise ValueError(
                f"runtime.keep_checkpoints "
                f"({self.runtime.keep_checkpoints}) must be >= 0 "
                "(newest checkpoints retained; 0 keeps everything)")
        if (self.runtime.snapshot_interval
                and self.replay.placement == "host"):
            raise ValueError(
                "runtime.snapshot_interval requires the device replay "
                "(replay.placement='device'): the host-replay numpy twin "
                "has no snapshot plane yet — set snapshot_interval=0 or "
                "switch placement")
        if self.telemetry.alerts_snapshot_stale_s <= 0:
            raise ValueError(
                f"telemetry.alerts_snapshot_stale_s "
                f"({self.telemetry.alerts_snapshot_stale_s}) must be > 0")
        if self.telemetry.alerts_recovery_loop < 1:
            raise ValueError(
                f"telemetry.alerts_recovery_loop "
                f"({self.telemetry.alerts_recovery_loop}) must be >= 1 "
                "(supervisor relaunches before the alert fires)")
        if self.telemetry.trace_sample_every < 1:
            raise ValueError(
                f"telemetry.trace_sample_every "
                f"({self.telemetry.trace_sample_every}) must be >= 1 "
                "(1 = trace every block/exchange)")
        if self.telemetry.alerts_spill_promotion_ms <= 0:
            raise ValueError(
                f"telemetry.alerts_spill_promotion_ms "
                f"({self.telemetry.alerts_spill_promotion_ms}) must be > 0")
        if self.telemetry.alerts_e2e_latency_growth <= 1:
            raise ValueError(
                f"telemetry.alerts_e2e_latency_growth "
                f"({self.telemetry.alerts_e2e_latency_growth}) must be > 1 "
                "(a multiple of the p50's rolling median)")
        if self.telemetry.ring_size < 16:
            raise ValueError(
                f"telemetry.ring_size ({self.telemetry.ring_size}) must be "
                ">= 16")
        if self.telemetry.flush_interval_s <= 0:
            raise ValueError("telemetry.flush_interval_s must be > 0")
        if self.telemetry.learning_interval < 1:
            raise ValueError(
                f"telemetry.learning_interval "
                f"({self.telemetry.learning_interval}) must be >= 1")
        if self.telemetry.learning_dq_batch < 1:
            raise ValueError(
                f"telemetry.learning_dq_batch "
                f"({self.telemetry.learning_dq_batch}) must be >= 1")
        if self.telemetry.nan_policy not in ("warn", "halt"):
            raise ValueError(
                f"telemetry.nan_policy ({self.telemetry.nan_policy!r}) must "
                "be 'warn' or 'halt'")
        if self.telemetry.resources_interval_s <= 0:
            raise ValueError("telemetry.resources_interval_s must be > 0")
        if not 0 <= self.telemetry.resources_headroom_warn_frac < 1:
            raise ValueError(
                f"telemetry.resources_headroom_warn_frac "
                f"({self.telemetry.resources_headroom_warn_frac}) must be "
                "in [0, 1)")
        if self.telemetry.alerts_window < 2:
            raise ValueError(
                f"telemetry.alerts_window ({self.telemetry.alerts_window}) "
                "must be >= 2")
        if not 0 < self.telemetry.alerts_throughput_drop_frac <= 1:
            raise ValueError(
                f"telemetry.alerts_throughput_drop_frac "
                f"({self.telemetry.alerts_throughput_drop_frac}) must be "
                "in (0, 1]")
        if self.telemetry.alerts_heartbeat_age_s < 0:
            raise ValueError(
                "telemetry.alerts_heartbeat_age_s must be >= 0")
        if self.telemetry.alerts_staleness_growth_factor <= 1:
            raise ValueError(
                f"telemetry.alerts_staleness_growth_factor "
                f"({self.telemetry.alerts_staleness_growth_factor}) must "
                "be > 1")
        if not 0 <= self.telemetry.alerts_hbm_headroom_frac < 1:
            raise ValueError(
                f"telemetry.alerts_hbm_headroom_frac "
                f"({self.telemetry.alerts_hbm_headroom_frac}) must be in "
                "[0, 1)")
        if self.telemetry.alerts_retrace_storm < 1:
            raise ValueError(
                f"telemetry.alerts_retrace_storm "
                f"({self.telemetry.alerts_retrace_storm}) must be >= 1")
        if self.telemetry.alerts_shard_imbalance <= 1:
            raise ValueError(
                f"telemetry.alerts_shard_imbalance "
                f"({self.telemetry.alerts_shard_imbalance}) must be > 1 "
                "(a max/min per-shard env-steps ratio; 1.0 = perfectly "
                "balanced)")
        if self.telemetry.replay_diag_interval < 1:
            raise ValueError(
                f"telemetry.replay_diag_interval "
                f"({self.telemetry.replay_diag_interval}) must be >= 1")
        if not 0 < self.telemetry.alerts_replay_ess_frac < 1:
            raise ValueError(
                f"telemetry.alerts_replay_ess_frac "
                f"({self.telemetry.alerts_replay_ess_frac}) must be in "
                "(0, 1)")
        if not 0 < self.telemetry.alerts_priority_saturation <= 1:
            raise ValueError(
                f"telemetry.alerts_priority_saturation "
                f"({self.telemetry.alerts_priority_saturation}) must be in "
                "(0, 1]")
        if self.telemetry.alerts_never_sampled_growth <= 1:
            raise ValueError(
                f"telemetry.alerts_never_sampled_growth "
                f"({self.telemetry.alerts_never_sampled_growth}) must be "
                "> 1 (a multiple of the fraction's rolling median)")
        if not 0 < self.telemetry.alerts_lane_starved_frac <= 1:
            raise ValueError(
                f"telemetry.alerts_lane_starved_frac "
                f"({self.telemetry.alerts_lane_starved_frac}) must be in "
                "(0, 1]")
        if self.telemetry.fleet_host_row_max_bytes < 0:
            raise ValueError(
                f"telemetry.fleet_host_row_max_bytes "
                f"({self.telemetry.fleet_host_row_max_bytes}) must be >= 0 "
                "(0 = unbounded)")
        if self.telemetry.alerts_rank_straggler <= 1:
            raise ValueError(
                f"telemetry.alerts_rank_straggler "
                f"({self.telemetry.alerts_rank_straggler}) must be > 1 "
                "(a max/min per-rank step-time ratio; 1.0 = "
                "perfectly balanced)")
        if not 0 < self.telemetry.alerts_lockstep_wait_frac <= 1:
            raise ValueError(
                f"telemetry.alerts_lockstep_wait_frac "
                f"({self.telemetry.alerts_lockstep_wait_frac}) must be in "
                "(0, 1]")
        if self.telemetry.alerts_fleet_desync <= 1:
            raise ValueError(
                f"telemetry.alerts_fleet_desync "
                f"({self.telemetry.alerts_fleet_desync}) must be > 1 "
                "(a max/min per-rank env-steps ratio)")
        if self.telemetry.alerts_missing_rank_age_s <= 0:
            raise ValueError(
                f"telemetry.alerts_missing_rank_age_s "
                f"({self.telemetry.alerts_missing_rank_age_s}) must be > 0")
        if not 0 <= self.serve.shadow_sample_rate <= 1:
            raise ValueError(
                f"serve.shadow_sample_rate ({self.serve.shadow_sample_rate}) "
                "must be in [0, 1]")
        if self.telemetry.quality_eval_interval_s <= 0:
            raise ValueError(
                f"telemetry.quality_eval_interval_s "
                f"({self.telemetry.quality_eval_interval_s}) must be > 0")
        if self.telemetry.quality_eval_rounds < 1:
            raise ValueError(
                f"telemetry.quality_eval_rounds "
                f"({self.telemetry.quality_eval_rounds}) must be >= 1")
        if self.telemetry.quality_eval_clients < 1:
            raise ValueError(
                f"telemetry.quality_eval_clients "
                f"({self.telemetry.quality_eval_clients}) must be >= 1")
        if self.telemetry.quality_calib_sample_every < 1:
            raise ValueError(
                f"telemetry.quality_calib_sample_every "
                f"({self.telemetry.quality_calib_sample_every}) must be "
                ">= 1")
        if not 0 < self.telemetry.alerts_quality_regression < 1:
            raise ValueError(
                f"telemetry.alerts_quality_regression "
                f"({self.telemetry.alerts_quality_regression}) must be in "
                "(0, 1) (a fraction of the rolling-median eval return)")
        if not 0 < self.telemetry.alerts_canary_divergence <= 1:
            raise ValueError(
                f"telemetry.alerts_canary_divergence "
                f"({self.telemetry.alerts_canary_divergence}) must be in "
                "(0, 1] (a greedy-disagreement fraction)")
        if self.telemetry.alerts_promotion_stall_s <= 0:
            raise ValueError(
                f"telemetry.alerts_promotion_stall_s "
                f"({self.telemetry.alerts_promotion_stall_s}) must be > 0")
        if not 0 <= self.fleet.promotion_canary_frac <= 1:
            raise ValueError(
                f"fleet.promotion_canary_frac "
                f"({self.fleet.promotion_canary_frac}) must be in [0, 1]")
        if not 0 <= self.fleet.promotion_divergence_bound <= 1:
            raise ValueError(
                f"fleet.promotion_divergence_bound "
                f"({self.fleet.promotion_divergence_bound}) must be in "
                "[0, 1] (a greedy-disagreement fraction)")
        if self.fleet.promotion_min_shadow < 0:
            raise ValueError(
                f"fleet.promotion_min_shadow "
                f"({self.fleet.promotion_min_shadow}) must be >= 0")
        if self.multiplayer.enabled and self.actor.envs_per_actor > 1:
            raise ValueError(
                "actor.envs_per_actor > 1 is not supported with multiplayer "
                "(host/join port wiring is per actor worker; extra lanes in "
                "one worker would collide on the game sockets — scale "
                "actor.num_actors instead)")
        if self.multiplayer.enabled and not (
                -1 <= self.multiplayer.player_id
                < self.multiplayer.num_players):
            raise ValueError(
                f"multiplayer.player_id ({self.multiplayer.player_id}) must "
                f"be -1 (whole population in-process) or in [0, "
                f"num_players={self.multiplayer.num_players})")

    # ---- derived helpers ----

    @property
    def seqs_per_block(self) -> int:
        return self.replay.block_length // self.sequence.learning_steps

    @property
    def num_blocks(self) -> int:
        return self.replay.capacity // self.replay.block_length

    @property
    def num_sequences(self) -> int:
        return self.replay.capacity // self.sequence.learning_steps

    def replace(self, **dotted: Any) -> "Config":
        """Return a new Config with dotted-path overrides applied.

        cfg.replace(**{"replay.capacity": 1000, "actor.num_actors": 4})
        """
        updates: Dict[str, Dict[str, Any]] = {}
        nested: Dict[Tuple[str, str], Dict[str, Any]] = {}
        for key, value in dotted.items():
            if "." not in key:
                raise KeyError(f"override key must be dotted (section.field): {key!r}")
            section, fname = key.split(".", 1)
            if "." in fname:
                # section.group.field: a section's own nested dataclass
                # (network.core.kind)
                group, leaf = fname.split(".", 1)
                if "." in leaf or (section, group) not in _NESTED_TYPES:
                    raise KeyError(f"no such nested override: {key!r}")
                nested.setdefault((section, group), {})[leaf] = value
            else:
                updates.setdefault(section, {})[fname] = value
        for (section, group), fields in nested.items():
            base = updates.setdefault(section, {}).get(
                group, getattr(getattr(self, section), group))
            updates[section][group] = dataclasses.replace(base, **fields)
        replaced = {}
        for section, fields in updates.items():
            sub = getattr(self, section)
            replaced[section] = dataclasses.replace(sub, **fields)
        return dataclasses.replace(self, **replaced)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Config":
        """Inverse of to_dict (tuples round-trip through JSON lists)."""
        kwargs = {}
        for f in dataclasses.fields(cls):
            # sections absent from the dict take their defaults: configs
            # serialized before a section existed (checkpoint .config.json
            # files) must keep loading after the schema grows
            sub = {key: value for key, value in (d.get(f.name) or {}).items()
                   if not _retired(f.name, key, value)}
            for key, value in sub.items():
                if isinstance(value, list):
                    sub[key] = tuple(
                        tuple(x) if isinstance(x, list) else x for x in value)
                elif (f.name, key) in _NESTED_TYPES:
                    sub[key] = _NESTED_TYPES[(f.name, key)](**value)
            kwargs[f.name] = _SECTION_TYPES[f.name](**sub)
        return cls(**kwargs)

    @classmethod
    def from_json(cls, text: str) -> "Config":
        return cls.from_dict(json.loads(text))


_SECTION_TYPES = {
    "env": EnvConfig, "network": NetworkConfig, "sequence": SequenceConfig,
    "replay": ReplayConfig, "optim": OptimConfig, "actor": ActorConfig,
    "serve": ServeConfig, "fleet": FleetConfig,
    "multiplayer": MultiplayerConfig,
    "mesh": MeshConfig, "runtime": RuntimeConfig,
    "telemetry": TelemetryConfig,
}

# a section's own nested dataclasses: (section, field) -> type
_NESTED_TYPES = {("network", "core"): CoreConfig}

# Options that no longer exist: (section, field) -> (the spellings under
# which an old config meant what the code now always does, or None where
# the field did nothing once the switch beside it was off; what took the
# path away). A checkpoint's .config.json or a launch script may still
# carry the key: at such a value it is dropped, at any other it is refused,
# from a dict (Config.from_dict) and from the command line (parse_overrides)
# alike. A PR that removes an option adds its row here.
_MEANT_OFF = ("off", "false", "0", "no")   # as resolve_pallas_setting reads
_RETIRED_FIELDS: Dict[Tuple[str, str],
                      Tuple[Optional[Tuple[str, ...]], str]] = {
    ("network", "space_to_depth"): (
        _MEANT_OFF, "PR 29: measured -31%, PERF.md §6"),
    ("network", "pallas_lstm"): (
        _MEANT_OFF, "PR 29: never compiled for the chip since round 4, "
        "PERF.md §6"),
    ("network", "pallas_lstm_block"): (None, ""),
    ("network", "pallas_lstm_interpret"): (None, ""),
    ("optim", "pallas_decode_layout"): (
        ("planar",), "PR 29: measured a loss, and the decode now follows the "
        "shapes, PERF.md §6"),
    ("optim", "fused_double_unroll"): (
        _MEANT_OFF, "PR 29: measured neutral, PERF.md §6"),
}


def _retired(section: str, fname: str, value: Any) -> bool:
    """Whether ``section.fname`` is a retired option at a value that may be
    dropped; at a value that asked for the removed path, ``ValueError``."""
    if (section, fname) not in _RETIRED_FIELDS:
        return False
    harmless, why = _RETIRED_FIELDS[(section, fname)]
    spelling = (str(int(value)) if isinstance(value, bool)
                else str(value)).lower()
    if harmless is not None and spelling not in harmless:
        raise ValueError(
            f"`{section}.{fname}={value}` was removed in {why}; the option "
            "no longer exists, drop it from the config")
    return True


# Field annotations are strings (PEP 563 via `from __future__ import
# annotations`); only scalar fields are CLI-settable.
_SCALAR_ANNOTATIONS = {"bool": bool, "int": int, "float": float, "str": str}


def _coerce(key: str, value: str, annotation: str) -> Any:
    if "Tuple[Tuple[int, int, int], ...]" in str(annotation):
        # conv-pyramid syntax: triples of out_channels,kernel,stride joined
        # by ';' — e.g. --network.conv_layers=8,4,2;16,3,1
        try:
            layers = tuple(
                tuple(int(x) for x in triple.split(","))
                for triple in value.split(";") if triple)
        except ValueError:
            layers = ()
        if not layers or any(len(t) != 3 for t in layers):
            raise SystemExit(
                f"invalid value {value!r} for {key!r}: expected "
                "';'-separated out_channels,kernel,stride triples, e.g. "
                "8,4,2;16,3,1")
        return layers
    if "Tuple[str, ...]" in str(annotation):
        # names joined by ',' (--network.core.layer_types=conv,full_attention)
        return tuple(name for name in value.split(",") if name)
    if str(annotation) == "Any":
        # union knob (actor.anakin_priority: a float stamp or "td") —
        # numeric strings become floats, anything else stays a string
        # and Config.__post_init__ validates the allowed spellings
        try:
            return float(value)
        except ValueError:
            return value
    target_type = _SCALAR_ANNOTATIONS.get(str(annotation).replace("Optional[str]", "str"))
    if target_type is None:
        raise SystemExit(
            f"cannot set {key!r} from the command line (field type {annotation}); "
            "construct the Config in code instead"
        )
    if target_type is bool:
        lowered = value.lower()
        if lowered in ("1", "true", "yes", "on"):
            return True
        if lowered in ("0", "false", "no", "off"):
            return False
        raise SystemExit(
            f"invalid value {value!r} for {key!r} (expected a boolean: "
            "1/0, true/false, yes/no, on/off)")
    if target_type is str:
        return value
    try:
        return target_type(value)
    except ValueError:
        raise SystemExit(
            f"invalid value {value!r} for {key!r} (expected {target_type.__name__})"
        ) from None


def parse_overrides(cfg: Config, argv: List[str]) -> Config:
    """Apply ``--section.field=value`` CLI overrides, type-coerced from the
    dataclass field annotations. Unknown keys raise."""
    dotted: Dict[str, Any] = {}
    for arg in argv:
        if not arg.startswith("--") or "=" not in arg:
            raise SystemExit(f"unrecognized argument {arg!r}; expected --section.field=value")
        key, _, raw = arg[2:].partition("=")
        section, _, fname = key.partition(".")
        if section not in {f.name for f in dataclasses.fields(cfg)}:
            raise SystemExit(f"unknown config section {section!r}")
        if _retired(section, fname, raw):
            continue
        sub = getattr(cfg, section)
        group, _, leaf = fname.partition(".")
        if leaf and (section, group) in _NESTED_TYPES:
            sub, fname = getattr(sub, group), leaf      # network.core.kind
        matching = {f.name: f for f in dataclasses.fields(sub)}
        if fname not in matching:
            raise SystemExit(f"unknown field {fname!r} in section {section!r}")
        dotted[key] = _coerce(key, raw, matching[fname].type)
    return cfg.replace(**dotted) if dotted else cfg


def apex_epsilon(actor_id: int, num_actors: int, base_eps: float,
                 alpha: float) -> float:
    """Ape-X per-actor epsilon ladder: eps_i = base ** (1 + i*alpha/(N-1))
    (ref train.py:16-18). Single-actor runs get base_eps. No defaults: the
    authoritative values live in ActorConfig (base_eps, eps_alpha)."""
    if num_actors <= 1:
        return base_eps
    return base_eps ** (1 + actor_id / (num_actors - 1) * alpha)


def vector_lane_epsilons(actor_idx: int, actor_cfg: ActorConfig,
                         total_actors: Optional[int] = None) -> List[float]:
    """Per-lane ε for one vectorized actor worker: the Ape-X ladder spread
    over ALL total_actors * envs_per_actor lanes in the fleet, with worker
    ``actor_idx`` owning the contiguous lane slice — so a fleet of vector
    actors explores exactly like the equally-sized scalar-actor fleet the
    reference runs (train.py:16-18). ``total_actors`` defaults to
    ``actor_cfg.num_actors`` (single-host); a multihost fleet passes its
    GLOBAL worker count (process_count * num_actors) alongside the global
    ``actor_idx``, mirroring the scalar path's global apex_epsilon."""
    if total_actors is None:
        total_actors = actor_cfg.num_actors
    if not 0 <= actor_idx < total_actors:
        raise ValueError(
            f"actor_idx {actor_idx} outside the fleet of {total_actors} "
            "workers — multihost callers must pass their global worker "
            "count as total_actors")
    k = actor_cfg.envs_per_actor
    total = total_actors * k
    return [apex_epsilon(actor_idx * k + lane, total, actor_cfg.base_eps,
                         actor_cfg.eps_alpha)
            for lane in range(k)]


# Fields eligible for population-based/genetic hyperparameter search, mirroring
# the reference's `<-- GEN` tags (ref config.py:12-57, README.md:28-32).
# Continuous fields carry a (lo, hi) range; fields constrained by the replay
# layout invariants (Config.__post_init__: learning_steps | block_length,
# block_length | capacity) or best kept hardware-friendly carry an explicit
# choice tuple, so samplers never draw layout-invalid configs.
GENETIC_SEARCH_SPACE: Dict[str, Dict[str, Any]] = {
    "optim.lr": {"range": (1e-5, 1e-3), "log": True},
    "optim.gamma": {"range": (0.99, 0.999)},
    "optim.target_net_update_interval": {"choices": (500, 1000, 2000, 2500, 5000)},
    "replay.batch_size": {"choices": (32, 64, 128, 256)},
    # multiples of block_length=400 (capacity % block_length == 0)
    "replay.capacity": {"choices": (50_000, 100_000, 200_000, 500_000, 1_000_000)},
    "replay.prio_exponent": {"range": (0.0, 1.0)},
    "replay.importance_sampling_exponent": {"range": (0.0, 1.0)},
    "sequence.burn_in_steps": {"choices": (0, 10, 20, 40, 80)},
    # divisors of block_length=400 (block_length % learning_steps == 0)
    "sequence.learning_steps": {"choices": (5, 8, 10, 16, 20)},
    "network.hidden_dim": {"choices": (128, 256, 512, 1024)},
    "network.cnn_out_dim": {"choices": (256, 512, 1024, 2048)},
    "network.use_dueling": {"choices": (False, True)},
}
