"""Tools tests: log parsing, genetic search mechanics (mock fitness), and the
plot CLI on a synthetic reference-format log."""

import json
import os

import numpy as np
import pytest

from r2d2_tpu.config import Config, GENETIC_SEARCH_SPACE
from r2d2_tpu.tools.genetic import (
    genome_to_config, mutate, run_search, sample_genome)
from r2d2_tpu.tools.logparse import parse_log


def _write_reference_style_log(path, n=12):
    """Emit exactly the reference's log line format (ref worker.py:220-234)."""
    with open(path, "w") as f:
        for i in range(n):
            f.write(f"buffer size: {1000 + i * 100}\n")
            f.write(f"buffer update speed: {50.0}/s\n")
            f.write(f"number of environment steps: {i * 1000}\n")
            if i % 2 == 0:
                f.write(f"average episode return: {float(i):.4f}\n")
            f.write(f"number of training steps: {i * 10}\n")
            f.write("training speed: 0.5/s\n")
            if i > 0:
                f.write(f"loss: {1.0 / (i + 1):.4f}\n")


def test_parse_reference_log(tmp_path):
    path = str(tmp_path / "train_player0.log")
    _write_reference_style_log(path)
    log = parse_log(path)
    assert len(log.buffer_sizes) == 12
    assert len(log.returns) == 6 and log.returns[0] == 0.0
    assert len(log.losses) == 11
    assert log.return_counts[1] == 3  # third interval (0-based count after 3 'buffer size' lines)
    assert log.env_steps[-1] == 11000


def test_plot_cli(tmp_path):
    _write_reference_style_log(str(tmp_path / "train_player0.log"))
    _write_reference_style_log(str(tmp_path / "train_player1.log"))
    out = str(tmp_path / "curves.png")
    from r2d2_tpu.cli.plot import main
    main(["--file_path", str(tmp_path), "--show_all", "--loss_interpolation",
          "--out", out])
    assert os.path.getsize(out) > 1000


def test_genome_sampling_always_valid():
    """Every sampled/mutated genome must construct a valid Config (the
    layout-safe space contract)."""
    rng = np.random.default_rng(0)
    base = Config()
    for _ in range(50):
        g = sample_genome(rng)
        g = mutate(rng, g, rate=0.5)
        cfg = genome_to_config(base, g)      # __post_init__ validates
        assert cfg.replay.block_length % cfg.sequence.learning_steps == 0
        assert isinstance(cfg.network.hidden_dim, int)
        assert isinstance(cfg.network.use_dueling, bool)


def test_slice_eval_pins_rate_limiter(monkeypatch):
    """Round-3 review: genetic fitness slices with the rate limiter off
    score scheduler noise (PERF.md measured 25-86 return on identical
    invocations). make_slice_eval must pin the collect:learn ratio unless
    the genome/base config already sets one."""
    from types import SimpleNamespace

    from r2d2_tpu.cli.genetic import make_slice_eval
    from r2d2_tpu.runtime import orchestrator as orch_mod

    captured = []

    def fake_train(cfg, **kwargs):
        captured.append(cfg)
        return [SimpleNamespace(
            metrics=SimpleNamespace(num_episodes=0, episode_reward=0.0))]

    monkeypatch.setattr(orch_mod, "train", fake_train)
    ev = make_slice_eval([], slice_steps=10, slice_seconds=10.0,
                         slice_ratio=2.0)
    ev(Config())                                   # default ratio 0 -> pinned
    assert captured[-1].replay.max_env_steps_per_train_step == 2.0
    explicit = Config().replace(
        **{"replay.max_env_steps_per_train_step": 1.5})
    ev(explicit)                                   # explicit value preserved
    assert captured[-1].replay.max_env_steps_per_train_step == 1.5
    ev0 = make_slice_eval([], 10, 10.0, slice_ratio=0.0)
    ev0(Config())                                  # 0 disables the pin
    assert captured[-1].replay.max_env_steps_per_train_step == 0.0
    # an EXPLICIT user 0 (free-run request) wins over the pin even though
    # it equals the dataclass default
    ev_user = make_slice_eval(
        ["--replay.max_env_steps_per_train_step=0"], 10, 10.0,
        slice_ratio=2.0)
    ev_user(Config())
    assert captured[-1].replay.max_env_steps_per_train_step == 0.0


def test_sync_eval_rejects_sub_one_ratio_and_bounds_wall_clock(tmp_path):
    """Round-4 review: sync collection IS the ratio schedule, so a <1
    effective ratio must be rejected up front (not silently score every
    genome -inf); and --slice-seconds must bound each sync genome (a
    timed-out genome scores -inf instead of stalling the generation)."""
    from r2d2_tpu.cli.genetic import make_sync_eval

    from tests.test_runtime import tiny_config

    with pytest.raises(ValueError, match="ratio >= 1"):
        make_sync_eval([], slice_steps=10, slice_ratio=0.0)

    # host placement breaks the bit-reproducibility contract: rejected
    from r2d2_tpu.tools.sync_train import sync_train
    host_cfg = tiny_config(tmp_path).replace(
        **{"replay.placement": "host",
           "replay.max_env_steps_per_train_step": 2.0})
    with pytest.raises(ValueError, match="placement='device'"):
        sync_train(host_cfg, 5, 0.4)

    ev = make_sync_eval([], slice_steps=10_000, slice_ratio=2.0,
                        max_seconds=0.5)
    assert np.isneginf(ev(tiny_config(tmp_path)))   # timed out -> -inf


@pytest.mark.slow
def test_identical_genome_scores_identically_in_sync_mode(tmp_path):
    """VERDICT r3 #6 'done' criterion (strengthened): two evaluations of
    the identical genome don't just land within tolerance — the default
    sync fitness mode is bit-reproducible, so they are EQUAL."""
    from r2d2_tpu.cli.genetic import make_sync_eval

    from tests.test_runtime import tiny_config

    cfg = tiny_config(tmp_path)
    ev = make_sync_eval([], slice_steps=30, slice_ratio=2.0)
    a, b = ev(cfg), ev(cfg)
    assert np.isfinite(a) and np.isfinite(b)
    assert a == b


def test_invalid_genome_scores_neg_inf_instead_of_crashing():
    """A user-overridden base can make sampled genomes invalid (e.g.
    block_length=20 vs the space's learning_steps=16): the search must
    score them -inf, not die at Config construction."""
    base = Config().replace(**{"replay.block_length": 20,
                               "replay.capacity": 800,
                               "sequence.learning_steps": 5,
                               "sequence.burn_in_steps": 4})
    seen = []

    def fitness(cfg: Config) -> float:
        seen.append(cfg)
        return float(cfg.optim.lr)

    history = run_search(fitness, base=base, population=8, generations=2,
                         seed=3)
    flat = [f for h in history for f in h.fitnesses]
    assert any(np.isneginf(f) for f in flat)       # invalid genomes scored
    assert any(np.isfinite(f) for f in flat)       # valid ones still ran
    assert seen                                    # eval_fn saw valid configs

    # an ALL-invalid generation (base conflicts with the whole space) must
    # fail loudly, not return a never-evaluated 'best' genome
    space = {"sequence.learning_steps": {"choices": (16,)}}   # 20 % 16 != 0
    with pytest.raises(ValueError, match="every genome"):
        run_search(fitness, base=base, population=4, generations=1,
                   seed=0, space=space)


def test_summarize_trace_aggregates_chrome_events(tmp_path):
    """summarize_trace: per-plane totals/counts from a Chrome trace, sorted
    by total span; device_plane picks the accelerator pid."""
    import gzip

    from r2d2_tpu.tools.profile_step import (
        device_plane, format_summary, summarize_trace)

    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 2, "name": "fusion.1", "dur": 100.0, "ts": 0},
        {"ph": "X", "pid": 2, "name": "fusion.1", "dur": 50.0, "ts": 1},
        {"ph": "X", "pid": 2, "name": "copy.2", "dur": 30.0, "ts": 2},
        {"ph": "X", "pid": 1, "name": "PjitFunction(step)", "dur": 10.0,
         "ts": 0},
    ]
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    summary = summarize_trace(str(tmp_path))
    assert summary["/device:TPU:0"][0] == ("fusion.1", 150.0, 2)
    assert summary["/device:TPU:0"][1] == ("copy.2", 30.0, 1)
    assert summary["/host:CPU"] == [("PjitFunction(step)", 10.0, 1)]
    plane, rows = device_plane(summary)
    assert plane == "/device:TPU:0" and rows[0][0] == "fusion.1"
    text = format_summary(summary, steps=2)
    assert "fusion.1" in text and "/device:TPU:0" in text

    with pytest.raises(FileNotFoundError):
        summarize_trace(str(tmp_path / "absent"))


@pytest.mark.slow
def test_profile_capture_end_to_end(tmp_path):
    """capture_step_trace profiles real fused steps at a tiny config and
    the summary contains the jitted step dispatch."""
    from r2d2_tpu.tools.profile_step import (
        capture_step_trace, summarize_trace, traced_step_count)

    from tests.test_runtime import tiny_config

    cfg = tiny_config(tmp_path)
    out = capture_step_trace(cfg, steps=3, out_dir=str(tmp_path / "trace"))
    # steps rounds UP to whole dispatches and is recorded alongside the
    # trace so re-analysis divides by what actually ran
    assert traced_step_count(out) == 3   # k=1 in tiny_config
    summary = summarize_trace(out)
    all_names = [n for rows in summary.values() for n, _, _ in rows]
    assert any("step" in n for n in all_names), all_names


def test_run_search_improves_mock_fitness():
    """GA must climb a simple deterministic objective (closer lr to 3e-4 and
    bigger hidden_dim is better)."""
    def fitness(cfg: Config) -> float:
        return (-abs(np.log10(cfg.optim.lr) - np.log10(3e-4))
                + cfg.network.hidden_dim / 1024.0)

    history = run_search(fitness, population=8, generations=5, seed=1)
    first_best = history[0].best[1]
    last_best = history[-1].best[1]
    assert last_best >= first_best
    # elitism: best fitness is monotonically non-decreasing
    bests = [h.best[1] for h in history]
    assert all(b2 >= b1 - 1e-9 for b1, b2 in zip(bests, bests[1:]))


def test_chip_checks_refuses_cpu_backend():
    """The on-chip pallas gate must refuse loudly on CPU (kernels do not
    lower there) instead of failing kernel-by-kernel."""
    from r2d2_tpu.tools.chip_checks import run_chip_checks
    assert run_chip_checks() == 2


def test_chip_smoke_refuses_without_tpu():
    """chip_smoke.py is the proof that the system starts ON THE CHIP: on
    any other platform it must stop before building anything, say which
    platform it found, print no result line and exit non-zero."""
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout and "runtime:" not in proc.stdout


def test_compile_cache_dir_env_or_fixed_checkout_path():
    """The compile cache can be placed from outside; otherwise it sits at
    ONE fixed, git-ignored path inside the checkout (pure function)."""
    from r2d2_tpu.utils.platform import COMPILE_CACHE_ENV, compile_cache_dir
    assert compile_cache_dir({COMPILE_CACHE_ENV: "/somewhere/else"}) == \
        "/somewhere/else"
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
    fixed = compile_cache_dir({})
    assert fixed == compile_cache_dir({COMPILE_CACHE_ENV: ""}) == \
        os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.slow
def test_soak_smoke_contract(tmp_path):
    """The production-soak CLI (VERDICT r4 #3) at toy scale: fill+wrap the
    ring, train with interleaved ingestion, checkpoint on cadence, emit
    the one-line JSON contract."""
    import json
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "r2d2_tpu.cli.soak", "--seconds=6",
         "--capacity=200", "--checkpoint-interval=3",
         f"--save-dir={tmp_path}",
         "--override", "env.frame_height=24",
         "--override", "env.frame_width=24",
         "--override", "env.frame_stack=2",
         "--override", "network.hidden_dim=32",
         "--override", "network.cnn_out_dim=32",
         "--override", "network.conv_layers=[[8,4,2],[16,3,1]]",
         "--override", "replay.block_length=20",
         "--override", "sequence.burn_in_steps=4",
         "--override", "sequence.learning_steps=5",
         "--override", "sequence.forward_steps=3",
         "--override", "replay.batch_size=8"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "soak"
    # OBSERVED wrap evidence from the replay state itself: the buffer is
    # full (capacity learning-steps) and the write pointer came back
    # around the ring after num_blocks + wrap_extra adds
    assert out["buffer_steps_after_fill"] == 200    # == capacity
    assert 0 < out["block_ptr_after_fill"] < out["num_blocks"]
    assert out["ring_laps_fill"] > 1.0
    assert out["ring_laps_train"] > 0           # ingestion during training
    assert out["train_steps"] > 0
    assert out["steps_per_sec_mean"] > 0
    assert len(out["checkpoint_save_s"]) >= 1   # cadence fired
    assert all(np.isfinite(x) for x in out["losses_sampled"])
