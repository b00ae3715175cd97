"""Which device a process runs on, said once: platform pinning for CPU-held
processes, the persistent compile cache, and the start-of-run report.

Pinning. JAX reads ``JAX_PLATFORMS`` when it is imported. A process that
must stay off the accelerator (actor children, sweep workers, the test
suite) often cannot set the variable early enough: under the ``spawn`` start
method the child re-imports the parent's main module — and with it jax —
before its target function runs. ``jax.config.update("jax_platforms", ...)``
still takes effect until the first backend initialisation, so the pins
below go through it as well as through the environment.
"""

import os
from typing import Mapping, Optional

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout root (the directory holding pyproject.toml)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_host_device_count(n: int) -> None:
    """Set the virtual CPU device count in XLA_FLAGS, REPLACING any existing
    ``--xla_force_host_platform_device_count`` (an inherited value from a
    parent test/driver process would otherwise win). Must run before the CPU
    backend initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


def pin_cpu_platform(n_devices: int) -> None:
    """Pin this process to an ``n_devices``-wide virtual CPU platform.

    The one blessed preamble for every CPU-pinned entry point (tests,
    multichip/multihost dryruns): env vars for fresh/child processes, then
    the jax.config route for a jax that is already imported (effective until
    the first backend initialization). Must run before any jax computation.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    force_host_device_count(n_devices)
    os.environ.setdefault("JAX_ENABLE_X64", "0")
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized; callers verify jax.devices()


def pin_platform(platform: Optional[str] = None) -> None:
    """Apply ``platform`` (default: the JAX_PLATFORMS env var) through
    jax.config — the route that still works when jax was imported before
    the variable was set (see the module docstring; this call is what
    keeps a spawned actor child off the learner's chip). No-op if no
    request or if a backend already initialized."""
    platform = platform or os.environ.get("JAX_PLATFORMS")
    if not platform:
        return
    import jax

    try:
        jax.config.update("jax_platforms", platform)
    except Exception:
        pass  # backends already initialized; the env var governed them


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it, else ONE fixed directory inside the checkout
    (git-ignored). Never a temp dir, pid or timestamp — the path is part of
    the cache key's stability: a directory that moves never hits."""
    environ = os.environ if environ is None else environ
    return environ.get(COMPILE_CACHE_ENV) or os.path.join(_REPO_ROOT,
                                                          ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for this process; every
    entry point calls this (after ``pin_platform``) before its first
    compile. Where the environment names the directory JAX has already
    adopted it and nothing is set here; otherwise the fixed in-checkout path
    is used. Returns the directory.

    A process pinned to the CPU is left alone (returns None): the cache is
    for accelerator programs. An XLA:CPU executable is machine code for the
    compiling host's CPU, and jaxlib 0.9.0's loader logs a multi-kilobyte
    machine-feature error for every cached CPU program it loads — even on
    the host that compiled it. Actor children are CPU-pinned and compile
    while the learner compiles its own, far longer, train program."""
    import jax

    if (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu":
        return None
    path = compile_cache_dir()
    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: a cold start here is dozens of sub-second
    # compiles around a few long ones, and they add up on every launch
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _libtpu_version() -> Optional[str]:
    try:
        from importlib.metadata import version
        return version("libtpu")
    except Exception:
        return None


def _decode_facts(cfg, bf16: bool, use_pallas: bool):
    """(route, lane fill) of the train step's observation decode for
    ``cfg``'s shapes, given what its bf16 and Pallas-decode switches
    resolved to: ``ops/pallas_kernels.py decode_route`` on the sampled
    window as ``learner/train_step.py _decode_inputs`` hands it over. The
    fill is None off the "lanes" route."""
    import jax.numpy as jnp

    from r2d2_tpu.ops.pallas_kernels import decode_route, lane_fill
    from r2d2_tpu.replay.structs import ReplaySpec

    spec = ReplaySpec.from_config(cfg)
    route = decode_route(
        (spec.batch_size, spec.seq_window + spec.frame_stack - 1,
         spec.stored_frame_height, spec.stored_frame_width),
        spec.seq_window, spec.frame_stack,
        use_pallas=use_pallas,
        out_dtype=jnp.bfloat16 if bf16 else jnp.float32)
    if route != "lanes":
        return route, None
    return route, round(lane_fill(spec.batch_size, spec.seq_window), 4)


def runtime_report(cfg) -> dict:
    """What this process actually runs on: platform, device kind and count,
    library versions, the compile-cache directory and the values
    ``cfg``'s "auto" switches resolved to on this backend. Running on a CPU
    is legitimate (tests, actors), so nothing here raises; the run just has
    to say what it is. Initializes the backend."""
    import jax
    import jax.numpy as jnp
    import jaxlib

    from r2d2_tpu.models.network import quant_compute_dtype
    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_setting as on

    devs = jax.devices()
    bf16 = on(cfg.network.bf16, "network.bf16")
    pallas_obs_decode = on(cfg.optim.pallas_obs_decode,
                           "optim.pallas_obs_decode")
    decode_layout, decode_lane_fill = _decode_facts(cfg, bf16,
                                                    pallas_obs_decode)
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _libtpu_version(),
        "compile_cache": (jax.config.jax_compilation_cache_dir
                          if jax.config.jax_enable_compilation_cache
                          else None),
        "resolved": {
            "bf16": bf16,
            "pallas_obs_decode": pallas_obs_decode,
            # the decode the train step's shapes take (static per shape),
            # and on the "lanes" route the share of the torso's batch that
            # is window frames (the rest pads the last lane tile)
            "decode_layout": decode_layout,
            "decode_lane_fill": decode_lane_fill,
            "pallas_sample_gather": on(cfg.replay.pallas_sample_gather,
                                       "replay.pallas_sample_gather"),
            "pallas_exact_gather": on(cfg.replay.pallas_exact_gather,
                                      "replay.pallas_exact_gather"),
            "steps_per_dispatch": cfg.runtime.resolved_steps_per_dispatch(),
            "ingest_batch_blocks":
                cfg.replay.resolved_ingest_batch_blocks(),
            "inference_dtype": cfg.network.inference_dtype,
            "quant_compute_dtype": jnp.dtype(quant_compute_dtype()).name,
        },
    }


def announce_runtime(cfg, logger=None) -> dict:
    """Say once, as a run's first line, what it runs on: prints the
    ``runtime_report`` as one ``runtime: key=value ...`` line (and writes it
    to ``logger`` when given). The trainer, the fused loop and the server
    call this before their first compile; returns the report."""
    report = runtime_report(cfg)
    flat = {k: v for k, v in report.items() if k != "resolved"}
    flat.update(report["resolved"])
    line = "runtime: " + " ".join(f"{k}={v}" for k, v in flat.items())
    print(line, flush=True)
    if logger is not None:
        logger.info(line)
    return report
