"""bench.py contract tests.

bench.py is one process that measures on the TPU it finds. These tests run
it as a subprocess on the CPU backend and assert:
  (a) without a TPU it exits non-zero, names the platform it found and
      prints no result;
  (b) the explicit R2D2_BENCH_SMOKE=1 CPU contract run still emits the
      one-line JSON, labelled ``"platform": "cpu"``;
  (c) an anomaly-flagged cell never elects the headline.
"""

import json
import os
import subprocess
import sys

import pytest

# every subprocess test here pays a jax import + smoke train: slow tier
pytestmark = pytest.mark.slow

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _run_bench(extra_env, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "R2D2_BENCH_SMOKE")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env)
    return subprocess.run([sys.executable, BENCH], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_bench_refuses_without_tpu():
    proc = _run_bench({})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""               # no number of any kind


def test_smoke_bench_emits_json_contract():
    proc = _run_bench({"R2D2_BENCH_SMOKE": "1"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "learner_sequence_updates_per_sec_per_chip"
    assert out["unit"] == "sequences/s"
    assert out["platform"] == "cpu"                # says what it ran on
    assert out["value"] > 0
    assert out["vs_baseline"] > 0
    assert out["matrix"]["f32_spd1"] == out["value"]
    # no peak rate for a device outside the one table
    assert "mfu_vs_bf16_peak" not in out
    # the matrix is self-describing (VERDICT r4 #5): every cell carries a
    # status, and null cells name WHY they are null
    assert out["cell_status"]["f32_spd1"] in ("ok", "ok-reused")
    for k, v in out["matrix"].items():
        if v is None:
            assert out["cell_status"][k].startswith(
                ("skipped:", "not-run", "failed:", "mosaic-reject")), (
                k, out["cell_status"][k])


def test_anomalous_default_cell_does_not_elect_headline():
    """assemble_output must not headline a value its own status says to
    disregard (code-review r5): an anomaly-flagged default cell falls back
    to the best clean cell."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ctx = {"default_label": "bf16_spd16", "batch_size": 128,
           "flops_per_step": 1e9, "peak": 0, "platform": "tpu",
           "device_kind": "fake"}
    matrix = {"f32_spd1": 6900.0, "bf16_spd16": 245.0}
    status = {"f32_spd1": "ok", "bf16_spd16": "anomaly"}
    out = bench.assemble_output({}, matrix, ctx, status)
    assert out["measured_config"] == "f32_spd1"
    assert out["value"] == 6900.0
    assert out["cell_status"]["bf16_spd16"] == "anomaly"
    # with a clean default the default cell elects as before
    status["bf16_spd16"] = "ok"
    matrix["bf16_spd16"] = 11290.0
    out = bench.assemble_output({}, matrix, ctx, status)
    assert out["measured_config"] == "bf16_spd16"
