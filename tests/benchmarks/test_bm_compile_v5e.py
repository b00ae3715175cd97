"""The cells' fused step programs compile for a described TPU v5e at their
real shapes and fit its memory with the ring, here, without the chip: a PR
that breaks a cell's compile or its fit fails tier-1 before it spends chip
time. A compile that passes is not a chip run and says nothing about speed.

The topology is described inside a module-scoped fixture (only one process
may hold the TPU's library, and every xdist worker imports this file): see
the on-chip-measurement guide, section 2. Keep these tests in this one file.
"""

import os

import jax
import pytest

from benchmarks import harness

V5E_HBM_BYTES = int(15.75 * 2**30)      # what the chip reports (PR 21)
ACTION_DIM = 6                           # the Fake env's

# what ``auto`` resolves to on a TPU; on this CPU it would resolve to off,
# so the test states it (the program gets no option for this)
TPU_SWITCHES = {
    "network.bf16": "on", "optim.pallas_obs_decode": "on",
    "replay.pallas_sample_gather": "on", "replay.pallas_exact_gather": "on",
    "runtime.steps_per_dispatch": 16,
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip, and warns; keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _cell_config(cell_name):
    from r2d2_tpu.config import Config
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, cell_name)
    overrides = harness.program_overrides(
        harness.config_doc(bench, cell["config"]),
        harness.traffic_doc(cell["traffic"]))
    return Config().replace(**{**overrides, **TPU_SWITCHES})


def _compile_step(cell_name, topo):
    """The step program ``Learner`` builds for the cell (same factory, same
    diagnostics), lowered from shapes placed on the described devices."""
    from jax.sharding import SingleDeviceSharding

    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_multi_learner_step)
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.replay.device_replay import replay_init
    from r2d2_tpu.replay.structs import ReplaySpec
    from r2d2_tpu.telemetry.learning import LearningDiag
    from r2d2_tpu.telemetry.replaydiag import ReplayDiag

    cfg = _cell_config(cell_name)
    spec = ReplaySpec.from_config(cfg)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    diag = dict(diag=LearningDiag.from_config(cfg),
                rdiag=ReplayDiag.from_config(cfg))
    train = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), net, cfg.optim))
    ring = jax.eval_shape(lambda: replay_init(spec))
    one_chip = SingleDeviceSharding(topo.devices[0])
    step = make_multi_learner_step(net, spec, cfg.optim,
                                   cfg.network.use_double, 16, **diag)
    args = _shapes(train, one_chip), _shapes(ring, one_chip)
    return spec, step.lower(*args).compile()


# every one-chip cell the learner runner drives, whichever PR added it
LEARNER_CELLS = [
    c["name"] for c in harness.load_benchmark()["workloads"]
    if c["chips"] == 1
    and harness.traffic_doc(c["traffic"])["runner"] == "learner"]


@pytest.mark.parametrize("cell_name", LEARNER_CELLS)
def test_fused_step_compiles_and_fits_v5e(cell_name, topo, no_compile_cache):
    with pytest.warns(UserWarning, match="pallas_exact_gather pads"):
        spec, compiled = _compile_step(cell_name, topo)
    text = compiled.as_text()
    # both kernels of the main path went through Mosaic
    assert text.count("tpu_custom_call") >= 2, "a Pallas kernel fell out"
    # the ring is an argument and is updated in place (donated)
    mem = compiled.memory_analysis()
    ring = spec.device_ring_bytes
    assert mem.argument_size_in_bytes >= ring
    assert mem.alias_size_in_bytes >= ring, "the ring is copied, not aliased"
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < V5E_HBM_BYTES, (
        f"{cell_name}: {peak / 2**30:.2f} GiB does not fit a v5e's "
        f"{V5E_HBM_BYTES / 2**30:.2f} GiB")
