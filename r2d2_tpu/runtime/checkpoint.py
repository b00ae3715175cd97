"""Checkpoint / resume (ref /root/reference/worker.py:311,380-381 +
SURVEY §5.4).

The reference torch.saves ``(state_dict, training_steps, env_steps)`` every
``save_interval`` learner steps and warm-starts weights-only via
``config.pretrain``. Here the full training state — params, target params,
optimizer state, step, env_steps — goes through orbax (atomic directory
writes, async-safe), and ``load_pretrain`` reproduces the weights-only
warm-start path for both learner and actors.

Checkpoint k lives at ``{save_dir}/{game}{k}_player{p}`` mirroring the
reference's ``{game}{k}_player{p}.pth`` naming (worker.py:381) so evaluation
sweeps iterate checkpoints the same way (test.py:30-32).
"""

import os
import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp


def _ckpt_dir(save_dir: str, game: str, index: int, player: int) -> str:
    return os.path.abspath(os.path.join(save_dir, f"{game}{index}_player{player}"))


def _solo_checkpointer() -> ocp.Checkpointer:
    """A checkpointer whose barrier set is ONLY the calling process.

    Under a multi-controller job (jax.process_count() > 1) orbax's default
    save synchronizes across every process — but the lockstep multihost
    trainer (parallel/multihost.py) checkpoints on rank 0 only, and the
    other ranks never enter the save, so the default barrier deadlocks
    (observed: loopback demo wedged at the first save boundary)."""
    if jax.process_count() > 1:
        me = jax.process_index()
        return ocp.Checkpointer(
            ocp.PyTreeCheckpointHandler(),
            multiprocessing_options=ocp.options.MultiprocessingOptions(
                primary_host=me, active_processes={me},
                barrier_sync_key_prefix=f"solo{me}"))
    return ocp.PyTreeCheckpointer()


def save_checkpoint(save_dir: str, game: str, index: int, player: int,
                    params, opt_state, target_params, step: int,
                    env_steps: int, config_json: Optional[str] = None) -> str:
    path = _ckpt_dir(save_dir, game, index, player)
    ckptr = _solo_checkpointer()
    payload = {
        "params": jax.device_get(params),
        "target_params": jax.device_get(target_params),
        "opt_state": jax.device_get(opt_state),
        "step": np.asarray(step, np.int64),
        "env_steps": np.asarray(env_steps, np.int64),
    }
    ckptr.save(path, payload, force=True)
    if config_json is not None:
        # the training Config rides next to the weights so evaluation can
        # rebuild the exact network (the reference's checkpoints silently
        # depend on config.py not having changed since training)
        with open(path + ".config.json", "w") as f:
            f.write(config_json)
    return path


def load_checkpoint_config(path: str):
    """Config stored by save_checkpoint, or None for config-less checkpoints."""
    cfg_path = os.path.abspath(path) + ".config.json"
    if not os.path.exists(cfg_path):
        return None
    from r2d2_tpu.config import Config
    with open(cfg_path) as f:
        return Config.from_json(f.read())


def restore_checkpoint(path: str, template: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    ckptr = ocp.PyTreeCheckpointer()
    try:
        if template is not None:
            return ckptr.restore(os.path.abspath(path), item=template)
        return ckptr.restore(os.path.abspath(path))
    except (ValueError, KeyError, TypeError) as e:
        # orbax structure mismatches surface as opaque tree errors; name the
        # most likely cause (the checkpoint predates an architecture change
        # — e.g. the round-3 LSTM param-tree rename) and the escape hatch
        raise ValueError(
            f"checkpoint at {path!r} does not match the current network's "
            "parameter tree — it was likely saved by an older architecture "
            "revision (parameter names/shapes changed). Re-train, or "
            "restore with an explicitly matching template.\n"
            f"original error: {type(e).__name__}: {e}") from e


def load_pretrain(path: str, params_template):
    """Weights-only warm start (ref worker.py:260-261,511-512): restores just
    ``params`` from a checkpoint directory, leaving optimizer/step fresh."""
    restored = restore_checkpoint(path)
    params = restored["params"] if isinstance(restored, dict) else restored

    # conform dtypes to the template; shape mismatches fail HERE with the
    # param's path named instead of surfacing later inside apply
    def conform(path_parts, t, p):
        t_arr, p_arr = np.asarray(t), np.asarray(p)
        if t_arr.shape != p_arr.shape:
            name = "/".join(str(getattr(k, "key", k)) for k in path_parts)
            raise ValueError(
                f"pretrain param {name!r} has shape {p_arr.shape}; the "
                f"current network expects {t_arr.shape} — architecture "
                "mismatch (network config differs from the checkpoint's)")
        return np.asarray(p_arr, t_arr.dtype)

    return jax.tree_util.tree_map_with_path(conform, params_template, params)


def resume_training_state(path: str, train_state):
    """Full resume (SURVEY §5.4): restore params, target_params, opt_state,
    step, and env_steps from a checkpoint into ``train_state``. Returns
    ``(new_train_state, env_steps)``. The RNG key is NOT checkpointed (the
    reference checkpoints no RNG either) — the carried key stays fresh."""
    template = {
        "params": jax.device_get(train_state.params),
        "target_params": jax.device_get(train_state.target_params),
        "opt_state": jax.device_get(train_state.opt_state),
        "step": np.asarray(0, np.int64),
        "env_steps": np.asarray(0, np.int64),
    }
    restored = restore_checkpoint(path, template)
    import jax.numpy as jnp
    from r2d2_tpu.learner.target import like_target
    new_state = train_state.replace(
        params=restored["params"],
        # a target saved as float32 (or narrowed) enters in the dtypes the
        # train state's target_of holds it in
        target_params=like_target(train_state.target_params,
                                  restored["target_params"]),
        opt_state=restored["opt_state"],
        step=jnp.asarray(int(restored["step"]), jnp.int32),
    )
    return new_state, int(restored["env_steps"])


def apply_restore(runtime_cfg, train_state) -> Tuple[Any, int]:
    """The one resume/warm-start policy, shared by the single-host Learner
    and the multihost lockstep trainer (so the rank-sensitive details —
    mutual exclusion, the pretrain target-params copy — cannot diverge).
    Returns ``(train_state, resumed_env_steps)``; a no-op without
    runtime.resume/pretrain."""
    if runtime_cfg.resume and runtime_cfg.pretrain:
        raise ValueError(
            "runtime.resume and runtime.pretrain are mutually exclusive — "
            "resume restores the full training state")
    if runtime_cfg.resume:
        return resume_training_state(runtime_cfg.resume, train_state)
    if runtime_cfg.pretrain:
        from r2d2_tpu.learner.target import like_target
        params = load_pretrain(runtime_cfg.pretrain, train_state.params)
        return train_state.replace(
            params=params,
            target_params=like_target(train_state.target_params, params)), 0
    return train_state, 0


def list_checkpoints(save_dir: str, game: str, player: int
                     ) -> List[Tuple[int, str]]:
    """Sorted (index, path) pairs, the eval sweep's iteration order
    (ref test.py:30-32)."""
    if not os.path.isdir(save_dir):
        return []
    pat = re.compile(re.escape(game) + r"(\d+)_player" + str(player) + r"$")
    out = []
    for name in os.listdir(save_dir):
        m = pat.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(save_dir, name)))
    return sorted(out)


def latest_checkpoint(save_dir: str, game: str, player: int
                      ) -> Optional[str]:
    """Path of the newest checkpoint, or None — the supervisor's resume
    target (runtime/supervisor.py picks up from here after a crash)."""
    ckpts = list_checkpoints(save_dir, game, player)
    return ckpts[-1][1] if ckpts else None


def prune_checkpoints(save_dir: str, game: str, player: int,
                      keep: int) -> List[str]:
    """Retention GC (ISSUE 18 satellite): delete all but the newest
    ``keep`` checkpoint directories for one player, each with its
    ``.config.json`` sidecar. Runs after every save — before this, disk
    growth was unbounded (every orbax dir holds the full param + opt
    tree). ``keep <= 0`` keeps everything. Returns the pruned paths.

    The rolling replay snapshot (replay/snapshot.py) is NOT pruned: it
    is one overwritten-in-place pair per player, not a per-checkpoint
    set, and the newest checkpoint resumes from it."""
    import shutil
    if keep <= 0:
        return []
    pruned = []
    for _idx, path in list_checkpoints(save_dir, game, player)[:-keep]:
        shutil.rmtree(path, ignore_errors=True)
        try:
            os.remove(path + ".config.json")
        except OSError:
            pass
        pruned.append(path)
    return pruned
