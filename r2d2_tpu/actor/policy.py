"""CPU-jitted actor policy — the reference's ``Network.step`` + ε-greedy
(/root/reference/model.py:67-84, /root/reference/worker.py:535-538) without
torch or Ray.

Actor processes run on host CPUs while the learner owns the TPU, so the
policy pins its params to the CPU backend: JAX placement follows committed
operands, making the same Flax module a CPU program here and a TPU program in
the learner — weight sync is a raw pytree copy, no format conversion
(the reference ships state_dicts through Ray's object store,
/root/reference/worker.py:286-290,572-576).

The policy owns the per-episode recurrent state and rolling frame stack
(ref worker.py:516,526,546-547, model.py:34,86-87).
"""

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.models.network import (NetworkApply,
                                     is_quant_bundle, make_inference_bundle,
                                     quantized_inference_apply)


def _pin_params(params, cpu, copy: bool):
    """CPU-resident params, REALLY copied when ``copy``. ``device_put``
    alone is wrong for in-process aliases: to the same device it is a
    no-op, and when the source is the learner's train_state — whose
    buffers are donated by the next fused step — the alias dies with it
    (observed as 'Buffer has been deleted or donated' in a
    single-process CPU run). ONE implementation for both actor policies."""
    if copy:
        params = jax.tree_util.tree_map(
            lambda x: np.array(x, copy=True), params)
    return jax.device_put(params, cpu)


def make_forward_fn(net: NetworkApply, inference_dtype: Optional[str] = None,
                    probe_interval: int = 0):
    """The ONE jitted acting forward (ISSUE 13 satellite): a (N, 1)
    single-step recurrent forward shared by ``ActorPolicy`` (N=1),
    ``BatchedActorPolicy``, and the central policy server
    (serve/server.py) — one definition of the acting forward across
    local and served inference, so parity between them is the identity
    of a single program, not a numerics argument.

    ``inference_dtype`` (default: ``net.config.inference_dtype``) is the
    quantized-inference knob (ISSUE 14) — because every consumer builds
    its forward HERE, flipping the config knob switches local actors,
    the policy server, and (through the same apply variant) the anakin
    scan together.

    At ``"f32"`` (the default) the program is byte-identical to pre-PR14:
    ``fn(params, stacked_obs, last_action, hidden)`` with ``stacked_obs``
    (N, H, W, stack) f32 in [0,1], ``last_action`` (N,) int32, ``hidden``
    (N, 2, hidden) packed — returns (greedy_actions (N,), q (N, A),
    hidden' (N, 2, hidden)).

    At ``"bf16"``/``"int8"`` the forward takes the PUBLISHED bundle
    ({"f32", "quant", "stamp"} — make_inference_bundle) plus a tick
    counter and the LIVE row count, and returns a 4th element, the
    accuracy probe: ``fn(bundle, stacked_obs, last_action, hidden,
    tick, live) -> (actions, q, hidden', (dq_max, agree_frac,
    probed))``. Every ``probe_interval``-th tick a ``lax.cond`` branch
    ALSO runs the f32 twin on the same live batch and emits
    max |Q_f32 − Q_quant| and the greedy-action agreement fraction over
    the first ``live`` rows (probed = 1.0) — the server pads
    under-filled dispatches to pow2 buckets, and degenerate pad rows
    must neither fire nor dilute quant_divergence; local policies pass
    live = N. Other ticks the branch is skipped and probed = 0.0.
    ``probe_interval=0`` compiles the probe OUT entirely — the
    program's weight arguments are then the quantized twin alone (what
    the costmodel's weight-bytes rows measure)."""
    mode = (inference_dtype if inference_dtype is not None
            else net.config.inference_dtype)

    if mode == "f32":
        def step_fn(params, stacked_obs, last_action, hidden):
            obs = stacked_obs[:, None]                     # (N, 1, ...)
            la = jax.nn.one_hot(last_action, net.action_dim,
                                dtype=jnp.float32)[:, None]
            q, h = net.module.apply(params, obs, la, hidden)
            return jnp.argmax(q[:, 0], axis=-1), q[:, 0], h

        return jax.jit(step_fn)

    from r2d2_tpu.models.network import f32_reference_module
    f32_module = f32_reference_module(net)
    interval = int(probe_interval)

    def quant_step_fn(bundle, stacked_obs, last_action, hidden, tick,
                      live):
        obs = stacked_obs[:, None]                         # (N, 1, ...)
        la = jax.nn.one_hot(last_action, net.action_dim,
                            dtype=jnp.float32)[:, None]
        q, h = quantized_inference_apply(net, bundle["quant"], obs, la,
                                         hidden)
        q = q[:, 0]
        actions = jnp.argmax(q, axis=-1)
        if interval > 0:
            def probe(_):
                q32, _h = f32_module.apply(bundle["f32"], obs, la, hidden)
                q32 = q32[:, 0]
                # first `live` rows only: the server's pow2 padding rows
                # are a fixed degenerate input, not policy behavior
                mask = (jnp.arange(q.shape[0]) <
                        jnp.asarray(live, jnp.int32))
                n = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
                dq = jnp.max(jnp.where(
                    mask[:, None], jnp.abs(q32 - q), 0.0))
                agree = jnp.sum(
                    ((jnp.argmax(q32, axis=-1) == actions) & mask
                     ).astype(jnp.float32)) / n
                return dq, agree, jnp.float32(1.0)

            probe_out = jax.lax.cond(
                jnp.asarray(tick, jnp.int32) % interval == 0, probe,
                lambda _: (jnp.float32(0.0), jnp.float32(0.0),
                           jnp.float32(0.0)),
                operand=None)
        else:
            probe_out = (jnp.float32(0.0), jnp.float32(0.0),
                         jnp.float32(0.0))
        return actions, q, h, probe_out

    return jax.jit(quant_step_fn)


def _force_f32(net: NetworkApply) -> NetworkApply:
    """Actors infer on host CPUs, where bf16 is emulated and slower —
    force the f32 compute policy regardless of the learner's (params are
    f32 storage under either policy, so the weight exchange is unchanged;
    the reference's amp is learner-only too, worker.py:309 vs the actors'
    plain CPU model worker.py:509)."""
    if net.config.bf16:
        import dataclasses
        h, w, s = net.obs_hw
        net = NetworkApply(net.action_dim,
                           dataclasses.replace(net.config, bf16=False),
                           s, h, w)
    return net


def feed_quant_probe(stats, probe_interval: int, probe, lanes: int,
                     tick: Optional[int] = None) -> None:
    """Route one forward's probe tuple (dq_max, agree_frac, probed) into
    a QuantStats — the ONE implementation shared by the local policies
    and the policy server's dispatch loop. No sink, a disabled probe,
    or an off-interval ``tick`` (the caller holds it host-side, so
    ``tick % interval`` is known BEFORE any device fetch) skips the
    three scalar fetches entirely."""
    if stats is None or probe_interval <= 0:
        return
    if tick is not None and tick % probe_interval != 0:
        return
    dq, agree, probed = (float(np.asarray(x)) for x in probe)
    if probed > 0.5:
        stats.on_probe(dq, agree, lanes=lanes)


class _QuantPolicyMixin:
    """The quantized-inference plumbing both local policies share
    (ISSUE 14): accept EITHER the published {"f32", "quant", "stamp"}
    bundle or raw params (a direct construction — eval, tests — gets a
    locally-built twin, stamp 0), drive the tick counter the in-graph
    probe keys on, and feed probe results / adopted publish stamps into
    the attached QuantStats. All no-ops at inference_dtype="f32"."""

    def _init_quant(self, net, quant_stats, probe_interval: int):
        self._quant = net.config.inference_dtype != "f32"
        self._quant_stats = quant_stats
        self._probe_interval = int(probe_interval) if self._quant else 0
        self._tick = 0

    def _prepare(self, params):
        """Bundle raw params for the quant forward (identity for a tree
        that already IS the published bundle, and at f32)."""
        if not self._quant or is_quant_bundle(params):
            return params
        return jax.device_get(make_inference_bundle(self.net, params))

    def _note_update(self, params) -> None:
        if self._quant and self._quant_stats is not None \
                and is_quant_bundle(params):
            self._quant_stats.on_stamp(int(np.asarray(params["stamp"])))

    def _feed_probe(self, probe, lanes: int) -> None:
        feed_quant_probe(self._quant_stats, self._probe_interval, probe,
                         lanes, tick=self._tick)


class ActorPolicy(_QuantPolicyMixin):
    def __init__(self, net: NetworkApply, params, epsilon: float, seed: int = 0,
                 copy_updates: bool = True, quant_stats=None,
                 quant_probe_interval: int = 0):
        net = _force_f32(net)
        self.net = net
        self.epsilon = float(epsilon)
        self.action_dim = net.action_dim
        self.rng = np.random.default_rng(seed)
        # local_devices, not devices: under a multihost (jax.distributed)
        # job jax.devices() is the GLOBAL list and index 0 is another
        # process's non-addressable device on every rank but 0
        self._cpu = jax.local_devices(backend="cpu")[0]
        # copy_updates=False: the transport hands over freshly-owned buffers
        # (WeightSubscriber.poll materializes a new copy per poll), so the
        # defensive copy in _pin would be a second full-tree copy per refresh
        self._copy_updates = copy_updates
        self._init_quant(net, quant_stats, quant_probe_interval)
        self.params = self._pin(self._prepare(params), copy=True)
        # the shared (N, 1) acting forward at N=1 — the exact program the
        # batched policy and the policy server run (inputs expand to the
        # same (1, 1, ...) shapes the old scalar closure built, so the
        # compiled computation is unchanged)
        self._fwd = make_forward_fn(net,
                                    probe_interval=self._probe_interval)
        self.reset_state()

    def _step(self, params, stacked, last_action, hidden, feed=True):
        if self._quant:
            action, q, h, probe = self._fwd(
                params, stacked[None], np.asarray(last_action)[None],
                hidden, np.int32(self._tick), np.int32(1))
            if feed:
                self._feed_probe(probe, lanes=1)
        else:
            action, q, h = self._fwd(params, stacked[None],
                                     np.asarray(last_action)[None], hidden)
        return action[0], q[0], h

    def reset_state(self) -> None:
        """Per-episode state reset (ref model.py:86-87, worker.py:584-591)."""
        self.hidden = jax.device_put(self.net.init_state(1), self._cpu)
        h, w, s = self.net.obs_hw
        self.stacked = np.zeros((h, w, s), np.float32)
        self.last_action = np.int32(-1)

    def observe_reset(self, obs: np.ndarray) -> None:
        """Fill the frame stack with the initial observation (ref worker.py:587)."""
        self.reset_state()
        self.stacked[:] = (np.asarray(obs, np.float32) / 255.0)[..., None]

    def observe(self, obs: np.ndarray, action: int) -> None:
        """Roll the frame stack and record the taken action (ref worker.py:543-547)."""
        self.stacked = np.roll(self.stacked, -1, axis=-1)
        self.stacked[..., -1] = np.asarray(obs, np.float32) / 255.0
        self.last_action = np.int32(action)

    def _pin(self, params, copy: bool):
        return _pin_params(params, self._cpu, copy)

    def update_params(self, params) -> None:
        self._note_update(params)
        self.params = self._pin(self._prepare(params),
                                copy=self._copy_updates)

    def step(self) -> Tuple[int, np.ndarray, np.ndarray]:
        """Greedy action + Q-values + packed hidden *after* this step; the
        ε-greedy override happens in ``act`` (ref worker.py:535-538)."""
        action, q, self.hidden = self._step(
            self.params, self.stacked, self.last_action, self.hidden)
        self._tick += 1
        return int(action), np.asarray(q), np.asarray(self.hidden[0])

    def act(self) -> Tuple[int, np.ndarray, np.ndarray]:
        action, q, hidden = self.step()
        if self.rng.random() < self.epsilon:
            action = int(self.rng.integers(self.action_dim))
        return action, q, hidden

    def bootstrap_q(self) -> np.ndarray:
        """Q at the current state without advancing the recurrent state —
        the block-boundary bootstrap (ref worker.py:560-563). feed=False:
        the tick doesn't advance here, so an on-interval bootstrap would
        otherwise feed the SAME tick's probe twice."""
        _, q, _ = self._step(self.params, self.stacked, self.last_action,
                             self.hidden, feed=False)
        return np.asarray(q)


class BatchedActorPolicy(_QuantPolicyMixin):
    """N env lanes through ONE jitted (N, 1) forward pass per tick.

    The scalar ActorPolicy pays a full jit dispatch + interpreter round-trip
    per env step; at N lanes the same recurrent forward amortizes both —
    the Podracer batching win (arxiv 2104.06272, and GPU Atari emulation's
    central measurement, arxiv 1907.08467). Per-lane state (rolling frame
    stack, packed LSTM hidden, last action) lives in host numpy so a single
    lane resets without touching the others; the Ape-X ε ladder assigns
    each lane its own ε and its own RNG stream, drawn in the scalar
    policy's exact order (one uniform per step, one integer draw only when
    exploring) so a lane is distributionally identical to the scalar actor
    it replaces.

    Numerics: the batched forward computes the same math as N scalar
    forwards, but XLA:CPU tiles its gemms differently at different batch
    sizes, so Q/hidden can differ from the scalar policy's by ~1 ulp
    (measured ≤ 1.2e-7 at f32); greedy actions are bit-identical whenever
    Q gaps exceed that (parity-tested in tests/test_actor_vector.py).
    """

    def __init__(self, net: NetworkApply, params,
                 epsilons: Sequence[float], seeds: Sequence[int],
                 copy_updates: bool = True, quant_stats=None,
                 quant_probe_interval: int = 0):
        if len(epsilons) != len(seeds):
            raise ValueError(
                f"epsilons ({len(epsilons)}) and seeds ({len(seeds)}) must "
                "have one entry per lane")
        net = _force_f32(net)
        self.net = net
        self.num_lanes = len(epsilons)
        self.epsilons = np.asarray(epsilons, np.float64)
        self.action_dim = net.action_dim
        # per-lane streams: lane i draws exactly like ActorPolicy(seed_i)
        self.rngs = [np.random.default_rng(s) for s in seeds]
        self._cpu = jax.local_devices(backend="cpu")[0]
        self._copy_updates = copy_updates
        self._init_quant(net, quant_stats, quant_probe_interval)
        self.params = self._pin(self._prepare(params), copy=True)
        # the shared acting forward (make_forward_fn) — identical closure
        # to the one this class used to define inline
        self._fwd = make_forward_fn(net,
                                    probe_interval=self._probe_interval)
        self.reset_state()

    def _step(self, params, stacked, last_action, hidden, feed=True):
        if self._quant:
            actions, q, h, probe = self._fwd(
                params, stacked, last_action, hidden,
                np.int32(self._tick), np.int32(self.num_lanes))
            if feed:
                self._feed_probe(probe, lanes=self.num_lanes)
            return actions, q, h
        return self._fwd(params, stacked, last_action, hidden)

    def reset_state(self) -> None:
        """Reset every lane's per-episode state."""
        h, w, s = self.net.obs_hw
        n = self.num_lanes
        # host numpy (not device arrays) so reset_lane mutates one row
        self.hidden = np.zeros((n, 2, self.net.state_half), np.float32)
        self.stacked = np.zeros((n, h, w, s), np.float32)
        self.last_action = np.full(n, -1, np.int32)

    def reset_lane(self, lane: int) -> None:
        self.hidden[lane] = 0.0
        self.stacked[lane] = 0.0
        self.last_action[lane] = -1

    def observe_reset_lane(self, lane: int, obs: np.ndarray) -> None:
        """Fill lane's frame stack with its episode-initial observation
        (the scalar policy's observe_reset, per lane)."""
        self.reset_lane(lane)
        self.stacked[lane] = (np.asarray(obs, np.float32) / 255.0)[..., None]

    def observe(self, obs: np.ndarray, actions: np.ndarray) -> None:
        """Roll every lane's frame stack and record the taken actions.
        obs: (N, H, W) uint8; actions: (N,)."""
        self.stacked = np.roll(self.stacked, -1, axis=-1)
        self.stacked[..., -1] = np.asarray(obs, np.float32) / 255.0
        self.last_action = np.asarray(actions, np.int32)

    def _pin(self, params, copy: bool):
        return _pin_params(params, self._cpu, copy)

    def update_params(self, params) -> None:
        self._note_update(params)
        self.params = self._pin(self._prepare(params),
                                copy=self._copy_updates)

    def step(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Greedy actions (N,), Q-values (N, A), and packed hiddens
        (N, 2, hidden) *after* this step; ε-greedy overrides happen in
        ``act``."""
        actions, q, hidden = self._step(
            self.params, self.stacked, self.last_action, self.hidden)
        self._tick += 1
        # np.array, not asarray: device output views are read-only, and
        # reset_lane mutates rows of this buffer in place
        self.hidden = np.array(hidden)
        return np.asarray(actions), np.asarray(q), self.hidden

    def act(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        actions, q, hidden = self.step()
        actions = np.array(actions)          # writable for the ε overrides
        for i, rng in enumerate(self.rngs):
            if rng.random() < self.epsilons[i]:
                actions[i] = int(rng.integers(self.action_dim))
        return actions, q, hidden

    def bootstrap_q(self) -> np.ndarray:
        """(N, A) Q at every lane's current state without advancing any
        recurrent state — the block-boundary bootstrap, one jitted call
        for all lanes (rows of reset lanes are unused by the caller).
        feed=False: the tick doesn't advance here (see ActorPolicy)."""
        _, q, _ = self._step(
            self.params, self.stacked, self.last_action, self.hidden,
            feed=False)
        return np.asarray(q)
