"""Learning-dynamics diagnostics (ISSUE 5): what the TRAINING is doing,
fused into the jitted step — the learner-side counterpart of the PR-4
systems telemetry.

Device side (``fused_diagnostics``, called from the train-step factories
when a :class:`LearningDiag` is passed):

  * fixed-bucket histograms of |TD error|, written-back priorities, and
    |Q(s,a)| — the SAME 64-bucket log layout as telemetry/histogram.py
    (edges reused verbatim; values read as raw magnitudes, not seconds),
    computed as a bucketize + scatter-add inside the jitted program: one
    log10 + one scatter per batch, no host round-trip (Podracer-style
    fused diagnostics, arXiv 2104.06272);
  * global + per-layer-group gradient norms (torso / lstm / head);
  * a non-finite guard on loss/grad-norm (the NaN forensics trigger);
  * sample staleness: the per-sequence weight-version stamps carried from
    the actors through replay (learner publish count − generation count);
  * every ``telemetry.learning_interval`` steps, under ``lax.cond`` so the
    steady-state step is untouched: target-network parameter distance and
    the paper's stored-state quality diagnostic ΔQ (Kapturowski et al.,
    ICLR 2019 §3/Fig. 4 — the R2D2 reproduction's first direct check that
    stored-state + burn-in actually works).

ΔQ definitions (the reproduction's proxy for the paper's ĥ): replay cannot
reconstruct the true episode-start state, so the REFERENCE Q is the
longest reconstruction it affords — a zero-state unroll over the sequence's
ENTIRE stored block row (up to burn_in + block_length steps of real
history vs the window's burn_in). Against that reference, at the learning
steps:

  * ``delta_q_stored``   — Q from the stored hidden + burn-in (training's
    own path) vs the reference, normalized by the reference's max |Q|;
    small ⇒ the stored-state strategy works;
  * ``delta_q_zero``     — Q from a zero hidden + burn-in vs the same
    reference; the stored/zero gap is the paper's Fig. 4 evidence;
  * ``delta_q_recomputed`` — the same stored-vs-reference discrepancy
    normalized by the TRAINING path's max |Q| instead; the
    (stored, recomputed) pair brackets the normalization choice.

Host side (:class:`LearningAggregator`): accumulates each dispatch's
device outputs without syncing, and at the metrics flush produces the ONE
``learning`` block of the periodic TrainMetrics record — plus the NaN
forensics: on the first non-finite loss/grad-norm it writes a one-shot
``nan_dump_player{p}.json`` (step, histograms, last batch idxes/ages, lr)
and applies ``telemetry.nan_policy`` (warn | halt).
"""

import json
import logging
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

# bucketize_values / value_counts are re-exported here for back-compat:
# they moved to telemetry/histogram.py (the ONE home of the bucket
# layout, host and device sides — ISSUE 10 satellite) so this pillar and
# replaydiag.py share a single scatter implementation.
from r2d2_tpu.telemetry.histogram import (  # noqa: F401
    NBUCKETS, bucketize_values, value_counts, value_summary)

_EPS = 1e-3          # ΔQ normalization floor (a near-zero max-Q state must
                     # not blow the ratio up)


@dataclass(frozen=True)
class LearningDiag:
    """Static (hashable) diagnostic spec closed over by the jitted train
    step — a distinct spec compiles a distinct program, exactly like
    ReplaySpec. ``None`` in the factories means diagnostics OFF and the
    compiled step is byte-identical to the pre-diagnostics program."""

    interval: int = 200       # learner steps between ΔQ / target-distance
    dq_batch: int = 16        # sequences per ΔQ evaluation

    @classmethod
    def from_config(cls, cfg) -> Optional["LearningDiag"]:
        """The ONE gating rule: learning diagnostics require BOTH the
        master telemetry switch and the learning kill switch."""
        t = cfg.telemetry
        if not (t.enabled and t.learning_enabled):
            return None
        return cls(interval=t.learning_interval, dq_batch=t.learning_dq_batch)


# ---------------------------------------------------------------------------
# Device-side pieces (jnp; traced into the fused step)


def group_grad_norms(grads) -> Dict[str, Any]:
    """Global-norm per top-level parameter group (torso / lstm / head for
    the R2D2 network; generic over whatever groups the param tree has)."""
    import optax
    groups = grads.get("params", grads) if isinstance(grads, dict) else grads
    return {str(k): optax.global_norm(v) for k, v in sorted(groups.items())}


def param_distance(params, target_params, net):
    """Global L2 distance between the online and target parameter trees.
    With use_double off the target is frozen at init, so this reads as
    total parameter drift since initialization instead. The online tree is
    first taken to the dtypes the target is held in (``learner/target.py
    target_of``), so that right after a sync this reads 0, and the
    difference is taken in float32."""
    import jax
    import jax.numpy as jnp
    import optax
    from r2d2_tpu.learner.target import target_of
    params = target_of(net, params)
    diff = jax.tree_util.tree_map(
        lambda p, t: p.astype(jnp.float32) - t.astype(jnp.float32), params,
        target_params)
    return optax.global_norm(diff)


def _window_q(net, spec, params, batch, hidden):
    """Full-window unroll of the sampled batch from an explicit hidden
    state — the diagnostic's own decode (always the jnp decode path: the
    cadence is too low for the pallas kernel to matter)."""
    import jax
    import jax.numpy as jnp
    from r2d2_tpu.ops.pallas_kernels import stack_frames
    stacked = stack_frames(batch.obs, spec.seq_window, spec.frame_stack,
                           use_pallas=False,
                           out_dtype=net.module.compute_dtype,
                           out_height=spec.frame_height,
                           out_width=spec.frame_width)
    la = jax.nn.one_hot(batch.last_action, net.action_dim, dtype=jnp.float32)
    q, _ = net.module.apply(params, stacked, la, hidden)
    return q                                              # (m, T, A) f32


def delta_q_diag(net, spec, params, batch, replay_state, dq_batch: int):
    """The stored-state quality diagnostic (module docstring): returns
    (delta_q_stored, delta_q_zero, delta_q_recomputed) f32 scalars.
    ``replay_state`` supplies the full block rows the reference unroll
    needs — device placement only (host placement reports NaN)."""
    import jax
    import jax.numpy as jnp
    from r2d2_tpu.ops.indexing import learning_step_mask, online_q_positions
    from r2d2_tpu.ops.pallas_kernels import stack_frames

    m = min(dq_batch, spec.batch_size)
    sub = jax.tree_util.tree_map(
        lambda x: x[:m] if x is not None else None, batch)

    q_stored = _window_q(net, spec, params, sub, sub.hidden)
    q_zero = _window_q(net, spec, params, sub, jnp.zeros_like(sub.hidden))

    # reference: zero-state unroll over the sequence's WHOLE stored row —
    # the longest context replay affords (timeline 0 .. seq_start covers
    # up to burn_in + block_length real steps of history)
    idx = sub.idxes
    b = idx // spec.seqs_per_block
    s = idx % spec.seqs_per_block
    seq_start = replay_state.seq_start[b, s]              # (m,)
    obs_full = replay_state.obs[b]                        # (m, row, Hs, Ws)
    la_full = replay_state.last_action[b]                 # (m, la_row_len)
    stacked = stack_frames(obs_full, spec.la_row_len, spec.frame_stack,
                           use_pallas=False,
                           out_dtype=net.module.compute_dtype,
                           out_height=spec.frame_height,
                           out_width=spec.frame_width)
    la_oh = jax.nn.one_hot(la_full, net.action_dim, dtype=jnp.float32)
    zeros = net.init_state(m)
    q_full, _ = net.module.apply(params, stacked, la_oh, zeros)  # (m, T', A)

    L = spec.learning
    lpos = seq_start[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
    q_rec = jnp.take_along_axis(q_full, lpos[:, :, None], axis=1)
    opos = online_q_positions(sub.burn_in_steps, L)
    q_s = jnp.take_along_axis(q_stored, opos[:, :, None], axis=1)
    q_z = jnp.take_along_axis(q_zero, opos[:, :, None], axis=1)
    mask = learning_step_mask(sub.learning_steps, L)      # (m, L)
    denom = jnp.maximum(mask.sum(), 1.0)

    def dq(q, ref):
        # the paper's per-state discrepancy ||q - q_ref||2 / |max_a q_ref|,
        # averaged over the valid learning steps of the sub-batch
        d = jnp.sqrt(jnp.sum((q - ref) ** 2, axis=-1))
        scale = jnp.max(jnp.abs(ref), axis=-1) + _EPS
        return jnp.sum(d / scale * mask) / denom

    return dq(q_s, q_rec), dq(q_z, q_rec), dq(q_rec, q_s)


def version_stats(weight_version) -> Dict[str, Any]:
    """Reduced staleness stats over a (B,) version-stamp vector, for paths
    that cannot return the raw vector (the manual dp-sharded step reduces
    these with pmin/pmax/pmean). -1 stamps mean 'unknown' (pre-stamp
    blocks) and are masked out; min/max saturate at 0/-1 when all are."""
    import jax.numpy as jnp
    v = weight_version.astype(jnp.float32)
    known = (v >= 0).astype(jnp.float32)
    n_known = jnp.maximum(known.sum(), 1.0)
    big = jnp.float32(2 ** 30)
    return {
        "ld/version_min": jnp.min(jnp.where(known > 0, v, big)),
        "ld/version_max": jnp.max(jnp.where(known > 0, v, -1.0)),
        "ld/version_mean": jnp.sum(v * known) / n_known,
        "ld/unknown_frac": 1.0 - known.sum() / v.shape[0],
    }


def fused_diagnostics(net, spec, diag: LearningDiag, new_step, params,
                      target_params, batch, aux, grads, loss, grad_norm,
                      replay_state=None, raw_arrays: bool = True
                      ) -> Dict[str, Any]:
    """The device-side diagnostic block, traced into the fused step.
    Returns a dict of ``ld/``-prefixed device values for the metrics
    pytree. ``raw_arrays=False`` (manual dp-sharded path) omits the
    per-sample vectors whose values differ across shards — the caller
    psums the histograms and pmeans the scalars instead."""
    import jax
    import jax.numpy as jnp

    out: Dict[str, Any] = {
        "ld/td_hist": value_counts(aux["abs_td"], aux["mask"]),
        "ld/prio_hist": value_counts(aux["priorities"]),
        "ld/q_hist": value_counts(aux["q_chosen"], aux["mask"]),
        "ld/grad_norm": grad_norm,
        "ld/nonfinite": jnp.logical_not(
            jnp.isfinite(loss) & jnp.isfinite(grad_norm)).astype(jnp.int32),
    }
    for name, g in group_grad_norms(grads).items():
        out[f"ld/grad_norm_{name}"] = g
    out.update(version_stats(batch.weight_version))
    if raw_arrays:
        out["ld/weight_versions"] = batch.weight_version
        out["ld/batch_idxes"] = batch.idxes

    # interval-gated heavies: lax.cond executes ONE branch at runtime, so
    # the reference unroll's cost lands only on diagnostic steps
    def on(_):
        tdist = param_distance(params, target_params, net)
        if replay_state is not None:
            dq_s, dq_z, dq_r = delta_q_diag(net, spec, params, batch,
                                            replay_state, diag.dq_batch)
        else:
            # host placement: the full block rows live off-device; the
            # windowed strategies alone cannot form the reference
            dq_s = dq_z = dq_r = jnp.float32(jnp.nan)
        return tdist, dq_s, dq_z, dq_r

    def off(_):
        nan = jnp.float32(jnp.nan)
        return nan, nan, nan, nan

    tdist, dq_s, dq_z, dq_r = jax.lax.cond(
        (new_step % diag.interval) == 0, on, off, operand=None)
    out["ld/target_dist"] = tdist
    out["ld/delta_q_stored"] = dq_s
    out["ld/delta_q_zero"] = dq_z
    out["ld/delta_q_recomputed"] = dq_r
    return out


# ---------------------------------------------------------------------------
# Host-side aggregation + NaN forensics


class MoeAggregator:
    """The routing counters of a core with experts (``models/cores/
    experts.py``: the ``mla_moe`` and ``conv_attn_moe`` cores) between two
    flushes: each dispatch's ``moe/`` outputs are summed on the device (a
    few hundred integers a step; no sync on the step path), and ``flush``
    fetches the
    sums once and makes the record's ``moe`` block, per expert layer: the
    histogram of chosen experts over all ``n_routed_experts``, the
    (position, expert) pairs that fell on the experts held here, the
    largest and the mean load among those, the router's entropy (nats, of
    the scores normalised over the experts, mean over positions and steps),
    the pairs dropped (always 0: the core has no capacity limit),
    ``rows_walked``, the sorted rows of the chunks the held experts walked
    (the first chunk, which holds the pairs a layer expects, and
    ``overflow_chunks`` x the overflow chunk's rows, summed over the steps):
    ``pairs_held`` over it is how full the walk was, and it over steps x
    all pairs the share of the pairs' rows that the experts' way there and
    back touched; ``overflow_chunks``, the chunks walked past the first:
    over the steps, how often the first chunk's margin was too small; and
    ``tile_rows``, the rows of the row tiles the grouped products visited
    for the held experts' groups (visits x the kernel's row tile, a tile
    that two experts share counted for each; from the groups' sizes alone,
    summed over the steps): ``pairs_held`` over it is how full the MXU's row
    tiles were, the number that says whether the tile fits the groups."""

    def __init__(self, core):
        self.held = slice(core.expert_offset,
                          core.expert_offset + core.experts_held)
        self._sums: Optional[Dict[str, Any]] = None
        self._steps = 0

    def on_dispatch(self, metrics: Dict[str, Any]) -> None:
        import jax.numpy as jnp
        moe = {k[len("moe/"):]: v for k, v in metrics.items()
               if k.startswith("moe/")}
        if not moe:
            return
        def total(v, tail):
            # (L_moe, ...) a step, or (K, L_moe, ...) a multi-step dispatch
            return jnp.reshape(v, (-1,) + v.shape[v.ndim - tail:]).sum(axis=0)

        flat = {k: total(v, 2 if k == "chosen" else 1)
                for k, v in moe.items()}
        self._steps += moe["entropy"].size // moe["entropy"].shape[-1]
        self._sums = flat if self._sums is None else {
            k: self._sums[k] + flat[k] for k in flat}

    def flush(self) -> Optional[dict]:
        import jax
        if self._sums is None:
            return None
        sums, steps = jax.device_get(self._sums), self._steps
        self._sums, self._steps = None, 0
        layers = []
        for chosen, entropy, dropped, walked, overflow, tile_rows in zip(
                sums["chosen"], sums["entropy"], sums["dropped"],
                sums["rows_walked"], sums["overflow_chunks"],
                sums["tile_rows"]):
            held = chosen[self.held]
            layers.append({
                "chosen_hist": [int(c) for c in chosen],
                "pairs_held": int(held.sum()),
                "held_load_max": int(held.max()),
                "held_load_mean": float(held.mean()),
                "router_entropy": float(entropy) / steps,
                "dropped": int(dropped),
                "rows_walked": int(walked),
                "overflow_chunks": int(overflow),
                "tile_rows": int(tile_rows),
            })
        return {"steps": steps, "layers": layers}


class CoreAggregator:
    """A core's own per-layer counters (``models/cores/__init__.py``: the
    ``core/`` outputs of each dispatch, its loss among them) between two
    flushes, summed on the device in float32 (a core may count some 3e7
    pairs a layer a step, which int32 would overflow within a hundred
    steps); ``flush`` hands the sums and the steps to the core's
    ``summarise``, which makes the record's blocks of them."""

    def __init__(self, summarise):
        self._summarise = summarise
        self._sums: Optional[Dict[str, Any]] = None
        self._steps = 0

    def on_dispatch(self, metrics: Dict[str, Any]) -> None:
        import jax.numpy as jnp
        own = {k[len("core/"):]: v for k, v in metrics.items()
               if k.startswith("core/")}
        if not own:
            return
        # (L,) a step, or (K, L) a multi-step dispatch
        flat = {k: jnp.reshape(v.astype(jnp.float32),
                               (-1, v.shape[-1])).sum(axis=0)
                for k, v in own.items()}
        first = next(iter(own.values()))
        self._steps += first.size // first.shape[-1]
        self._sums = flat if self._sums is None else {
            k: self._sums[k] + flat[k] for k in flat}

    def flush(self) -> Optional[dict]:
        import jax
        if self._sums is None:
            return None
        sums = {k: np.asarray(v, np.float64)
                for k, v in jax.device_get(self._sums).items()}
        steps = self._steps
        self._sums, self._steps = None, 0
        return self._summarise(sums, steps)


def _flatten_rows(values: List[np.ndarray], width: int) -> np.ndarray:
    """Stack per-dispatch histogram outputs — (width,) per step or
    (K, width) per multi-step dispatch — into one (n, width) matrix."""
    return np.concatenate(
        [np.asarray(v).reshape(-1, width) for v in values], axis=0)


def _last_finite(values: List[np.ndarray]) -> Optional[float]:
    if not values:
        return None
    flat = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                           for v in values])
    finite = flat[np.isfinite(flat)]
    return float(finite[-1]) if finite.size else None


class LearningAggregator:
    """Host-side accumulator for the fused step's ``ld/`` outputs: holds
    device values between metric flushes (no sync on the step path), then
    produces the periodic record's ``learning`` block in ONE device_get —
    and owns the NaN forensics (one-shot dump + nan_policy)."""

    def __init__(self, player_idx: int, save_dir: str, nan_policy: str,
                 lr: float):
        self.player_idx = player_idx
        self.save_dir = save_dir or "."
        self.nan_policy = nan_policy
        self.lr = lr
        self.nan_dumped = False
        self._pending: List[Dict[str, Any]] = []

    def on_dispatch(self, metrics: Dict[str, Any]) -> None:
        ld = {k: v for k, v in metrics.items() if k.startswith("ld/")}
        if ld:
            self._pending.append(ld)

    @property
    def dump_path(self) -> str:
        return os.path.join(self.save_dir,
                            f"nan_dump_player{self.player_idx}.json")

    def flush(self, host_step: int, publish_count: Optional[int] = None,
              occupancy_versions: Optional[List[int]] = None
              ) -> Optional[dict]:
        """Aggregate the interval and return the ``learning`` record block
        (None when no training steps ran). ``publish_count`` is the weight
        service's CURRENT publication counter (ages are measured against
        it — the flush-time value, a one-interval skew at most);
        ``occupancy_versions`` the per-ring-slot generation stamps for the
        replay-occupancy age percentiles."""
        import jax
        if not self._pending:
            return None
        pending, self._pending = self._pending, []
        host = jax.device_get(pending)

        def col(key):
            return [d[key] for d in host if key in d]

        block: Dict[str, Any] = {}
        for name, key in (("td_abs", "ld/td_hist"),
                          ("priority", "ld/prio_hist"),
                          ("q_abs", "ld/q_hist")):
            rows = col(key)
            if rows:
                counts = _flatten_rows(rows, NBUCKETS).sum(axis=0)
                block[name] = value_summary(counts)
                block[name + "_counts"] = [int(c) for c in counts]

        gn: Dict[str, Optional[float]] = {}
        for key in sorted({k for d in host for k in d
                           if k.startswith("ld/grad_norm")}):
            flat = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                                   for v in col(key)])
            name = key[len("ld/grad_norm"):].lstrip("_") or "global"
            gn[name] = (round(float(np.max(flat)), 6),
                        round(float(np.mean(flat)), 6))
        block["grad_norm"] = {k: {"max": mx, "mean": mean}
                              for k, (mx, mean) in gn.items()}

        block["target_param_dist"] = _last_finite(col("ld/target_dist"))
        dq = {name: _last_finite(col(f"ld/delta_q_{name}"))
              for name in ("stored", "zero", "recomputed")}
        block["delta_q"] = dq if any(v is not None for v in dq.values()) \
            else None

        block["sample_age"] = self._sample_ages(host, col, publish_count)
        block["replay_age"] = self._occupancy_ages(publish_count,
                                                   occupancy_versions)
        nonfinite = int(sum(int(np.asarray(v).sum())
                            for v in col("ld/nonfinite")))
        block["nonfinite_steps"] = nonfinite
        if nonfinite:
            self._on_nonfinite(host_step, block, host)
        return block

    def _sample_ages(self, host, col, publish_count) -> Optional[dict]:
        """Sample-age distribution: learner publish count − generation
        stamp, over every sequence trained this interval. Raw stamps when
        the step returned them; the sharded paths' reduced stats
        otherwise. -1 stamps (pre-PR5 blocks) report as unknown."""
        raw = col("ld/weight_versions")
        if raw and publish_count is not None:
            v = np.concatenate([np.asarray(x).reshape(-1) for x in raw])
            known = v[v >= 0]
            out = {"unknown_frac": round(1.0 - known.size / max(v.size, 1),
                                         4)}
            if known.size:
                ages = np.maximum(publish_count - known.astype(np.int64), 0)
                out.update({
                    "p50": float(np.percentile(ages, 50)),
                    "p95": float(np.percentile(ages, 95)),
                    "max": int(ages.max()),
                    "mean": round(float(ages.mean()), 3),
                })
            return out
        vmax = col("ld/version_max")
        if vmax and publish_count is not None:
            mx = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                                 for v in vmax])
            mn = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                                 for v in col("ld/version_min")])
            uf = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                                 for v in col("ld/unknown_frac")])
            known_mx = mx[mx >= 0]
            if known_mx.size == 0:
                return {"unknown_frac": 1.0}
            return {
                # min version = max age and vice versa
                "max": int(max(publish_count - float(np.min(
                    mn[mn < 2 ** 29])), 0)) if np.any(mn < 2 ** 29) else 0,
                "min": int(max(publish_count - float(np.max(known_mx)), 0)),
                "unknown_frac": round(float(np.mean(uf)), 4),
            }
        return None

    def _occupancy_ages(self, publish_count,
                        occupancy_versions) -> Optional[dict]:
        if publish_count is None or not occupancy_versions:
            return None
        v = np.asarray([x for x in occupancy_versions if x >= 0], np.int64)
        if v.size == 0:
            return {"unknown_slots": len(occupancy_versions)}
        ages = np.maximum(publish_count - v, 0)
        return {
            "p50": float(np.percentile(ages, 50)),
            "p95": float(np.percentile(ages, 95)),
            "max": int(ages.max()),
            "slots": int(v.size),
            "unknown_slots": len(occupancy_versions) - int(v.size),
        }

    def _on_nonfinite(self, host_step: int, block: dict, host) -> None:
        """The forensic path: first non-finite loss/grad-norm of the run
        writes ONE dump record, then nan_policy decides warn vs halt."""
        log = logging.getLogger(__name__)
        if not self.nan_dumped:
            self.nan_dumped = True
            last = host[-1]
            dump = {
                "step": int(host_step),
                "time": time.time(),
                "lr": self.lr,
                "nan_policy": self.nan_policy,
                "learning": {k: v for k, v in block.items()
                             if not k.endswith("_counts")},
                "histograms": {k: block[k] for k in
                               ("td_abs_counts", "priority_counts",
                                "q_abs_counts") if k in block},
                "last_batch_idxes": [
                    int(x) for x in np.asarray(
                        last.get("ld/batch_idxes", [])).reshape(-1)],
                "last_batch_weight_versions": [
                    int(x) for x in np.asarray(
                        last.get("ld/weight_versions", [])).reshape(-1)],
            }
            try:
                os.makedirs(self.save_dir, exist_ok=True)
                with open(self.dump_path, "w") as f:
                    json.dump(dump, f, indent=2)
            except OSError:
                log.exception("failed writing NaN forensics dump")
            log.warning(
                "player %d: NON-FINITE loss/grad-norm at step ~%d — "
                "forensics dumped to %s (telemetry.nan_policy=%s)",
                self.player_idx, host_step, self.dump_path, self.nan_policy)
        if self.nan_policy == "halt":
            raise RuntimeError(
                f"non-finite loss/grad-norm at step ~{host_step} "
                f"(telemetry.nan_policy=halt); forensics at "
                f"{self.dump_path}")
