"""Runtime integration tests: weight service, metrics log format, checkpoint
round-trip, and the hermetic end-to-end training slice on the fake env
(SURVEY §4 — the multi-process/system behavior the reference never tests).
"""

import os
import re

import jax
import numpy as np
import pytest

from r2d2_tpu.config import Config
from r2d2_tpu.models import init_network
from r2d2_tpu.runtime.checkpoint import (
    list_checkpoints, load_pretrain, restore_checkpoint, save_checkpoint)
from r2d2_tpu.runtime.metrics import TrainMetrics
from r2d2_tpu.runtime.orchestrator import train
from r2d2_tpu.runtime.weights import (
    InProcWeightStore, WeightPublisher, WeightSubscriber)


def tiny_config(tmp_path, **overrides) -> Config:
    cfg = Config().replace(**{
        "env.game_name": "Fake",
        "env.frame_height": 24, "env.frame_width": 24, "env.frame_stack": 2,
        "network.hidden_dim": 16, "network.cnn_out_dim": 32,
        "network.conv_layers": ((8, 4, 2), (16, 3, 1)),
        "sequence.burn_in_steps": 4, "sequence.learning_steps": 5,
        "sequence.forward_steps": 3,
        "replay.capacity": 800, "replay.block_length": 20,
        "replay.batch_size": 8, "replay.learning_starts": 100,
        "actor.num_actors": 2, "actor.actor_update_interval": 50,
        "optim.lr": 1e-3,
        "runtime.save_dir": str(tmp_path), "runtime.save_interval": 50,
        "runtime.log_interval": 0.2, "runtime.weight_publish_interval": 5,
        # per-step dispatch: these tests assert per-step cadences (publish,
        # checkpoint, step counts); the production default is 16
        "runtime.steps_per_dispatch": 1,
    })
    return cfg.replace(**overrides) if overrides else cfg


@pytest.fixture
def small_params():
    from r2d2_tpu.config import NetworkConfig
    _, params = init_network(
        jax.random.PRNGKey(0), 4,
        NetworkConfig(hidden_dim=8, cnn_out_dim=16,
                      conv_layers=((4, 3, 2),)),
        frame_stack=2, frame_height=12, frame_width=12)
    return params


def test_weight_shm_roundtrip(small_params):
    """Publisher → shm → subscriber returns the identical pytree; repeated
    polls without a publish return None (version gate)."""
    pub = WeightPublisher(small_params)
    try:
        sub = WeightSubscriber(pub.name, small_params)
        got = sub.poll()
        assert got is not None
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
            small_params, got)
        assert sub.poll() is None
        bumped = jax.tree_util.tree_map(lambda x: x + 1.0, small_params)
        pub.publish(bumped)
        got2 = sub.poll()
        leaves = jax.tree_util.tree_leaves(got2)
        orig = jax.tree_util.tree_leaves(small_params)
        np.testing.assert_allclose(np.asarray(leaves[0]),
                                   np.asarray(orig[0]) + 1.0)
        sub.close()
    finally:
        pub.close()


def test_weight_shm_checksum_path(small_params, monkeypatch):
    """The non-TSO validation path (VERDICT r4 #6): with _NEEDS_CHECKSUM
    forced on, (a) the roundtrip still works (crc written + validated), and
    (b) a payload corrupted AFTER the version settled — the torn-read shape
    a weakly-ordered host can produce — is rejected instead of returned."""
    from r2d2_tpu.runtime import weights as W
    monkeypatch.setattr(W, "_NEEDS_CHECKSUM", True)
    pub = WeightPublisher(small_params)
    try:
        sub = WeightSubscriber(pub.name, small_params)
        got = sub.poll()
        assert got is not None
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                    np.asarray(b)),
            small_params, got)
        # simulate a torn publish: bump the version to a NEW even value
        # (so the version gate alone would accept) but corrupt the payload
        # relative to the stored crc
        bumped = jax.tree_util.tree_map(lambda x: x + 1.0, small_params)
        pub.publish(bumped)
        pub._payload[0] += 123.0
        assert sub.poll() is None          # crc mismatch -> rejected
        # a clean re-publish recovers
        pub.publish(bumped)
        assert sub.poll() is not None
        sub.close()
    finally:
        pub.close()


def test_inproc_store_per_reader_versions(small_params):
    store = InProcWeightStore(small_params)
    assert store.poll(0) is not None
    assert store.poll(0) is None
    assert store.poll(1) is not None  # second reader still sees v1
    store.publish(small_params)
    assert store.poll(0) is not None


def test_metrics_reference_log_format(tmp_path):
    """Emitted keys must match the reference's exact strings so its plot.py
    parses our logs (ref worker.py:220-234, plot.py:33-48)."""
    m = TrainMetrics(player_idx=0, log_dir=str(tmp_path))
    m.set_buffer_size(1234)
    m.on_block(20, episode_return=7.5)
    m.on_train_step(0.25)
    m.on_train_step(0.35)
    m.log(20.0)
    text = (tmp_path / "train_player0.log").read_text()
    assert re.search(r"^buffer size: 1234$", text, re.M)
    assert re.search(r"^buffer update speed: .*/s$", text, re.M)
    assert re.search(r"^number of environment steps: 20$", text, re.M)
    assert re.search(r"^average episode return: 7\.5000$", text, re.M)
    assert re.search(r"^number of training steps: 2$", text, re.M)
    assert re.search(r"^training speed: .*/s$", text, re.M)
    assert re.search(r"^loss: 0\.3000$", text, re.M)


def test_checkpoint_roundtrip_and_pretrain(tmp_path, small_params):
    import optax
    opt_state = optax.adam(1e-4).init(small_params)
    path = save_checkpoint(str(tmp_path), "Fake", 3, 0, small_params,
                           opt_state, small_params, step=300, env_steps=9000)
    assert os.path.isdir(path)
    restored = restore_checkpoint(path)
    assert int(restored["step"]) == 300 and int(restored["env_steps"]) == 9000
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        small_params, restored["params"])
    warm = load_pretrain(path, small_params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        small_params, warm)
    assert list_checkpoints(str(tmp_path), "Fake", 0) == [(3, path)]


@pytest.mark.slow
def test_full_resume_continues_exactly(tmp_path):
    """Train K steps → checkpoint → resume → continued run matches the
    uninterrupted run bit-for-bit (params AND opt_state restored; the
    reference can only warm-start weights, worker.py:260-261)."""
    import numpy.random as npr
    from r2d2_tpu.config import NetworkConfig, OptimConfig
    from r2d2_tpu.learner import create_train_state
    from r2d2_tpu.learner.train_step import make_external_batch_step
    from r2d2_tpu.replay import replay_add, replay_init
    from r2d2_tpu.replay.device_replay import replay_sample
    from r2d2_tpu.runtime.checkpoint import (
        resume_training_state, save_checkpoint)
    from tests.test_replay import A, _fill_blocks, make_spec

    rng = npr.default_rng(0)
    spec = make_spec(batch_size=8)
    ncfg = NetworkConfig(hidden_dim=spec.hidden_dim, cnn_out_dim=16,
                         conv_layers=((8, 4, 2), (16, 3, 1)))
    net, _ = init_network(jax.random.PRNGKey(0), A, ncfg,
                          frame_stack=spec.frame_stack,
                          frame_height=spec.frame_height,
                          frame_width=spec.frame_width)
    opt = OptimConfig(lr=1e-3)
    rs = replay_init(spec)
    for blk in _fill_blocks(spec, 3, rng):
        rs = replay_add(spec, rs, blk)
    batch = replay_sample(spec, rs, jax.random.PRNGKey(7))
    step = make_external_batch_step(net, spec, opt, use_double=False)

    ts = create_train_state(jax.random.PRNGKey(1), net, opt)
    for _ in range(3):
        ts, _m = step(ts, batch)
    path = save_checkpoint(str(tmp_path), "Fake", 1, 0, ts.params,
                           ts.opt_state, ts.target_params, int(ts.step),
                           env_steps=123)
    for _ in range(3):
        ts, _m = step(ts, batch)          # uninterrupted continuation

    # resume into a DIFFERENTLY-seeded fresh state: everything must come
    # from the checkpoint, nothing from the fresh init
    ts2 = create_train_state(jax.random.PRNGKey(99), net, opt)
    ts2, env_steps = resume_training_state(path, ts2)
    assert env_steps == 123
    assert int(ts2.step) == 3
    for _ in range(3):
        ts2, _m = step(ts2, batch)

    assert int(ts.step) == int(ts2.step) == 6
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        ts.params, ts2.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        ts.opt_state, ts2.opt_state)


def test_learner_resume_wiring(tmp_path):
    """cfg.runtime.resume restores step/env_steps into the Learner; resume
    and pretrain are mutually exclusive."""
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner

    cfg = tiny_config(tmp_path)
    net = NetworkApply(4, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    learner = Learner(cfg, net)
    path = learner.save(2)
    learner.env_steps = 0  # save() recorded env_steps=0

    cfg2 = cfg.replace(**{"runtime.resume": path})
    resumed = Learner(cfg2, net)
    assert resumed.training_steps == int(learner.train_state.step)
    assert resumed.env_steps == 0

    with pytest.raises(ValueError, match="mutually exclusive"):
        Learner(cfg.replace(**{"runtime.resume": path,
                               "runtime.pretrain": path}), net)


def test_supervisor_restarts_dead_actor(tmp_path):
    """PlayerStack.supervise respawns dead actor threads (failure handling
    the reference lacks entirely, SURVEY §5.3)."""
    import threading
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.runtime.orchestrator import PlayerStack

    cfg = tiny_config(tmp_path)
    probe = create_env(cfg.env)
    stack = PlayerStack(cfg, 0, probe.action_space.n)
    stop = threading.Event()
    stack.start_actors_threads(stop)
    try:
        assert all(t.is_alive() for t in stack.threads)
        # simulate a crashed actor: a thread that already finished
        dead = threading.Thread(target=lambda: None)
        dead.start(); dead.join()
        stack.threads[0] = dead
        assert stack.supervise() == 1
        assert stack.threads[0].is_alive()
        # stop requested: no restart
        stack.threads[0] = dead
        stop.set()
        assert stack.supervise() == 0
    finally:
        stop.set()
        stack.close()


def test_supervisor_disabled_by_config(tmp_path):
    """runtime.restart_dead_actors=False disables RESPAWNING: the health
    scan still runs (hang detection, failure accounting) but a dead
    worker stays dead."""
    import threading
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.runtime.orchestrator import PlayerStack

    cfg = tiny_config(tmp_path, **{"runtime.restart_dead_actors": False})
    probe = create_env(cfg.env)
    stack = PlayerStack(cfg, 0, probe.action_space.n)
    stop = threading.Event()
    stack.start_actors_threads(stop)
    try:
        dead = threading.Thread(target=lambda: None)
        dead.start(); dead.join()
        stack.threads[0] = dead
        assert stack.supervise() == 0
        assert not stack.threads[0].is_alive()
    finally:
        stop.set()
        stack.close()


def test_ring_recovery_runs_with_restarts_disabled(tmp_path):
    """Round-3 advisor: with runtime.restart_dead_actors=False a producer
    dying between reserve and commit must STILL trigger shm-slot
    reclamation — otherwise the wedged head slot starves the learner even
    though other actors are alive."""
    import threading
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.runtime.orchestrator import PlayerStack

    cfg = tiny_config(tmp_path, **{"runtime.restart_dead_actors": False})
    probe = create_env(cfg.env)
    stack = PlayerStack(cfg, 0, probe.action_space.n)
    probe.close()
    stack._stop = threading.Event()

    class DeadProc:
        def is_alive(self):
            return False

    class StubQueue:
        recoveries = 0

        def recover_stalled(self):
            self.recoveries += 1
            return 1

    stack.processes = [DeadProc()]
    stack.queue = StubQueue()
    try:
        sched = stack._ring_recovery
        assert stack.supervise() == 0            # no restart...
        assert sched._after is not None          # ...but recovery scheduled
        sched._after = 0.0                       # skip the 6s slot grace
        assert stack.supervise() == 0
        assert stack.queue.recoveries == 1
        # the death was < 6s ago: a follow-up pass re-arms (the slot may
        # not have been stale for the pass that just ran)
        assert sched._after is not None
        sched._last_death = 0.0                  # grace has long passed
        sched._after = 0.0
        assert stack.supervise() == 0
        assert stack.queue.recoveries == 2
        assert sched._after is None              # disarmed
        # the same permanently-dead process must not reschedule every tick
        assert stack.supervise() == 0
        assert sched._after is None
        assert stack.queue.recoveries == 2
    finally:
        # release the stack's process-wide state (shm boards, span drain,
        # the compile monitor's logger hook) — the stubs aren't closeable
        stack.processes = []
        stack.queue = None
        stack.close()


def test_thread_actor_envs_closed_on_stop(tmp_path, monkeypatch):
    """Round-3 advisor: actor thread exit (clean stop or crash) must close
    its env — a respawn creates a fresh one, so an unclosed predecessor
    leaks fds/engine handles per restart."""
    import threading
    from r2d2_tpu.envs import factory as factory_mod
    from r2d2_tpu.runtime import orchestrator as orch_mod

    closed = []
    real_create = factory_mod.create_env

    def tracking_create(*args, **kwargs):
        env = real_create(*args, **kwargs)
        orig_close = env.close
        env.close = lambda: (closed.append(env), orig_close())[1]
        return env

    monkeypatch.setattr(orch_mod, "create_env", tracking_create)
    cfg = tiny_config(tmp_path)
    probe = factory_mod.create_env(cfg.env)
    stack = orch_mod.PlayerStack(cfg, 0, probe.action_space.n)
    probe.close()
    stop = threading.Event()
    stack.start_actors_threads(stop)
    n = cfg.actor.num_actors
    assert len(stack.threads) == n
    stop.set()
    stack.close()
    assert len(closed) == n


def test_pretrain_names_a_first_conv_of_another_shape(tmp_path,
                                                       small_params):
    """A checkpoint whose first-conv kernel has another shape than the
    network's (another stack, another kernel; what a checkpoint of the
    removed space-to-depth layout looks like: kernel halved, channels x4)
    is refused by name; nothing is rewritten on the way in."""
    path = save_checkpoint(str(tmp_path), "Fake", 1, 0, small_params,
                           {"dummy": np.zeros(1)}, small_params, 0, 0)
    template = jax.tree_util.tree_map(np.asarray, small_params)
    kh, kw, c, o = template["params"]["torso"]["Conv_0"]["kernel"].shape
    template["params"]["torso"]["Conv_0"]["kernel"] = np.zeros(
        (kh * 2, kw * 2, c // 2, o), np.float32)
    with pytest.raises(ValueError, match=r"torso/Conv_0/kernel.*\(3, 3, 2, 4\)"
                                         r".*architecture mismatch"):
        load_pretrain(path, template)


@pytest.mark.slow
def test_end_to_end_training_slice(tmp_path):
    """The minimum end-to-end slice (SURVEY §7.3): thread actors on the fake
    env feed the device replay; the fused learner trains; checkpoints, logs,
    and weight publication all happen."""
    cfg = tiny_config(tmp_path)
    stacks = train(cfg, max_training_steps=15, max_seconds=300,
                   actor_mode="thread")
    learner = stacks[0].learner
    assert int(learner.train_state.step) >= 15
    assert learner.env_steps >= cfg.replay.learning_starts
    # step-0 checkpoint written (ref worker.py:311)
    assert any(idx == 0 for idx, _ in list_checkpoints(str(tmp_path), "Fake", 0))
    log = (tmp_path / "train_player0.log")
    assert log.exists()


def test_put_patient_blocks_until_space_and_honors_stop():
    """The patient put survives back-pressure (a full queue) until space
    appears, and gives up promptly when the stop signal fires."""
    import threading
    import time as time_mod

    from r2d2_tpu.runtime.feeder import BlockQueue

    q = BlockQueue(maxsize=1, use_mp=False)
    assert q.put_patient("a", should_stop=lambda: False, poll=0.05)

    # full queue: put_patient parks until a consumer drains
    done = []
    t = threading.Thread(
        target=lambda: done.append(
            q.put_patient("b", should_stop=lambda: False, poll=0.05)))
    t.start()
    time_mod.sleep(0.2)
    assert t.is_alive() and not done          # parked, not failed
    # drain exactly one: a full drain races the just-woken producer, which
    # can slip "b" in between two get_nowait calls
    assert q.drain(max_items=1) == ["a"]
    t.join(timeout=5.0)
    assert done == [True] and q.drain() == ["b"]

    # full queue + stop: returns False instead of blocking forever
    q.put_patient("c", should_stop=lambda: False, poll=0.05)
    t0 = time_mod.time()
    assert q.put_patient("d", should_stop=lambda: True, poll=0.05) is False
    assert time_mod.time() - t0 < 1.0


def test_rate_limiter_pauses_and_resumes_ingestion(tmp_path):
    """replay.max_env_steps_per_train_step pins the collect:learn ratio:
    ingestion pauses once env_steps exceed learning_starts + ratio *
    train_steps and resumes as training advances (Reverb-style rate
    limiting; the reference's actors free-run, worker.py:528)."""
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.feeder import BlockQueue
    from r2d2_tpu.runtime.learner_loop import Learner

    from tests.test_replay import _fill_blocks

    # frame/hidden dims matched to test_replay's synthetic block driver
    cfg = tiny_config(tmp_path, **{
        "replay.max_env_steps_per_train_step": 2.0,
        "env.frame_height": 12, "env.frame_width": 12,
        "network.hidden_dim": 8})
    probe = create_env(cfg.env)
    net = NetworkApply(probe.action_space.n, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    probe.close()
    learner = Learner(cfg, net)

    rng = np.random.default_rng(0)
    q = BlockQueue(use_mp=False)
    for blk in _fill_blocks(learner.spec, 12, rng):
        q.put(blk)

    # pre-training budget = learning_starts(100) + 2.0*1: 20-step blocks
    # ingest until env_steps reaches 120, then pause
    ingested = 0
    while learner.drain(q, max_items=1):
        ingested += 1
    assert learner.env_steps == 120 and ingested == 6
    assert learner.ingestion_paused
    assert learner.drain(q) == 0          # still parked

    # training advances -> budget moves -> ingestion resumes
    learner._host_step = 50               # budget = 100 + 2.0*50 = 200
    assert not learner.ingestion_paused
    while learner.drain(q, max_items=1):
        ingested += 1
    assert learner.env_steps == 200 and ingested == 10
    assert learner.ingestion_paused


@pytest.mark.slow
def test_dropped_priority_writebacks_are_counted(tmp_path):
    """Round-3 review: under write-back queue backpressure the host-mode
    learner drops priority updates (degrading PER toward uniform) — that
    must be observable: TrainMetrics.dropped_priority_updates increments
    and the JSONL record carries it."""
    import queue as queue_mod
    import threading

    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.feeder import BlockQueue
    from r2d2_tpu.runtime.learner_loop import Learner

    from tests.test_replay import _fill_blocks

    cfg = tiny_config(tmp_path, **{
        "replay.placement": "host", "runtime.save_interval": 0,
        "env.frame_height": 12, "env.frame_width": 12,
        "network.hidden_dim": 8})
    probe = create_env(cfg.env)
    net = NetworkApply(probe.action_space.n, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    probe.close()
    learner = Learner(cfg, net)

    q = BlockQueue(use_mp=False)
    for blk in _fill_blocks(learner.spec, 6, np.random.default_rng(0)):
        q.put(blk)
    while learner.drain(q, max_items=1):
        pass
    assert learner.ready

    # Saturate the write-back path: stall the consumer inside
    # update_priorities and shrink the queue to one slot, so the second or
    # third step's put_nowait hits Full and the drop must be counted.
    release = threading.Event()
    orig_update = learner.host_replay.update_priorities

    def stalled_update(*args, **kwargs):
        release.wait(timeout=60)
        return orig_update(*args, **kwargs)

    learner.host_replay.update_priorities = stalled_update
    learner._writeback_q = queue_mod.Queue(maxsize=1)
    try:
        for _ in range(4):
            learner.step()
        assert learner.metrics.dropped_priority_updates >= 1
        rec = learner.metrics.log(1.0)
        assert (rec["dropped_priority_updates"]
                == learner.metrics.dropped_priority_updates)
    finally:
        release.set()
        learner.stop_background()


def test_rate_limiter_survives_resume(tmp_path):
    """Regression (round-3 review): the limiter budget must be measured
    from the process's starting point. A resumed run restores large
    cumulative env/train counters while its replay ring restarts empty —
    an absolute budget comparison would pause ingestion forever and
    training could never reach learning_starts again."""
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.feeder import BlockQueue
    from r2d2_tpu.runtime.learner_loop import Learner

    from tests.test_replay import _fill_blocks

    cfg = tiny_config(tmp_path, **{
        "replay.max_env_steps_per_train_step": 2.0,
        "env.frame_height": 12, "env.frame_width": 12,
        "network.hidden_dim": 8})
    probe = create_env(cfg.env)
    net = NetworkApply(probe.action_space.n, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    probe.close()

    first = Learner(cfg, net)
    first.env_steps = 9_999            # steady-state cumulative counter
    ckpt = first.save(7)

    resumed = Learner(cfg.replace(**{"runtime.resume": ckpt}), net)
    assert resumed.env_steps == 9_999
    assert not resumed.ingestion_paused   # empty ring: must accept data

    q = BlockQueue(use_mp=False)
    rng = np.random.default_rng(0)
    for blk in _fill_blocks(resumed.spec, 8, rng):
        q.put(blk)
    ingested = 0
    while resumed.drain(q, max_items=1):
        ingested += 1
    # fresh budget from the resume point: learning_starts(100)+2.0 -> 6
    # blocks of 20 steps, then pause — training can start
    assert ingested == 6 and resumed.ready
    assert resumed.ingestion_paused


def test_rate_limiter_never_pauses_before_dp_gate_opens(tmp_path):
    """Regression (round-3 review): under a dp mesh the ready gate also
    waits for one block per shard. The limiter must not pause ingestion
    while that gate is closed — the budget can be exhausted after shard 0's
    block, and pausing there would starve shard 1 forever (drain() returns
    0, ready stays False, training never starts: livelock)."""
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.feeder import BlockQueue
    from r2d2_tpu.runtime.learner_loop import Learner

    from tests.test_replay import _fill_blocks

    # one 20-step block already exceeds budget = learning_starts(10) + 2.0
    cfg = tiny_config(tmp_path, **{
        "mesh.dp": 2, "replay.learning_starts": 10,
        "replay.max_env_steps_per_train_step": 2.0,
        "env.frame_height": 12, "env.frame_width": 12,
        "network.hidden_dim": 8})
    probe = create_env(cfg.env)
    net = NetworkApply(probe.action_space.n, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    probe.close()
    learner = Learner(cfg, net)

    q = BlockQueue(use_mp=False)
    for blk in _fill_blocks(learner.spec, 2, np.random.default_rng(0)):
        q.put(blk)

    assert learner.drain(q, max_items=1) == 1    # shard 0 filled
    assert not learner.ready                     # shard 1 still empty
    assert not learner.ingestion_paused          # must keep accepting
    assert learner.drain(q, max_items=1) == 1    # shard 1 filled
    assert learner.ready                         # training can start
    assert learner.ingestion_paused              # NOW the ratio applies


@pytest.mark.slow
def test_end_to_end_process_mode(tmp_path):
    """The production actor topology (VERDICT r2 #4): spawned actor
    processes feeding the learner over the native shm block ring with
    shared-memory weight subscription (the reference's deployed mode is Ray
    actors over plasma, worker.py:502-591 + train.py:36-43). Asserts the
    learner trains from process-produced blocks and that close() leaves no
    orphan processes."""
    import time as time_mod

    cfg = tiny_config(tmp_path, **{"runtime.save_interval": 0})
    stacks = train(cfg, max_training_steps=10, max_seconds=600,
                   actor_mode="process")
    learner = stacks[0].learner
    assert learner.training_steps >= 10
    # blocks crossed the process boundary and filled the buffer — through
    # the native shm ring when the toolchain is present (default transport)
    assert learner.env_steps >= cfg.replay.learning_starts
    try:
        from r2d2_tpu.native import ring_lib
        ring_lib()   # probes the actual native build, not just the import
        native_ok = True
    except Exception:
        native_ok = False
    if native_ok:
        from r2d2_tpu.runtime.shm_feeder import ShmBlockRing
        assert isinstance(stacks[0].queue._q, ShmBlockRing)
    procs = stacks[0].processes
    assert len(procs) == cfg.actor.num_actors
    deadline = time_mod.time() + 10.0
    while any(p.is_alive() for p in procs) and time_mod.time() < deadline:
        time_mod.sleep(0.1)
    assert not any(p.is_alive() for p in procs), "orphan actor processes"
    # shm weight segment was unlinked by close()
    assert stacks[0].publisher is not None


@pytest.mark.slow
def test_end_to_end_mesh_dp2(tmp_path):
    """mesh.dp=2 routes the production Learner onto the shard_map step and
    the dp-sharded replay (SURVEY §5.8): thread actors feed blocks
    round-robin across shards, gradients pmean over the mesh, and the
    orchestrator loop never knows the difference."""
    cfg = tiny_config(tmp_path, **{"mesh.dp": 2, "runtime.save_interval": 0})
    stacks = train(cfg, max_training_steps=6, max_seconds=300,
                   actor_mode="thread")
    learner = stacks[0].learner
    assert learner.mesh is not None and learner.mesh.shape["dp"] == 2
    assert learner.training_steps >= 6
    # the replay ring really is sharded: leading dp axis
    assert learner.replay_state.obs.shape[0] == 2
    assert int(learner.replay_state.learning_steps[0].sum()) > 0
    assert int(learner.replay_state.learning_steps[1].sum()) > 0
    for leaf in jax.tree_util.tree_leaves(learner.train_state.params):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.slow
def test_end_to_end_host_placement(tmp_path):
    """The reference-style architecture (replay.placement="host"): CPU ring +
    native sum tree + prefetch/write-back threads, external-batch device
    step."""
    cfg = tiny_config(tmp_path, **{"replay.placement": "host",
                                   "runtime.save_interval": 0})
    stacks = train(cfg, max_training_steps=10, max_seconds=300,
                   actor_mode="thread")
    learner = stacks[0].learner
    assert learner.host_mode
    assert learner.training_steps >= 10
    assert len(learner.host_replay) >= cfg.replay.learning_starts
    # close() (already run by train()) must have joined the pipeline threads
    assert not any(t.is_alive() for t in learner._bg_threads)
    assert not learner._bg_threads


@pytest.mark.slow
def test_end_to_end_host_placement_tensor_parallel(tmp_path):
    """mesh.mp=2 with replay.placement='host' routes the production Learner
    onto the tensor-parallel external-batch step: wide params genuinely
    sharded over mp, batches placed over dp, training proceeds through the
    full orchestrator."""
    cfg = tiny_config(tmp_path, **{
        "replay.placement": "host", "mesh.mp": 2, "mesh.dp": 2,
        "runtime.save_interval": 0})
    stacks = train(cfg, max_training_steps=6, max_seconds=300,
                   actor_mode="thread")
    learner = stacks[0].learner
    assert learner.host_mode and learner.training_steps >= 6
    # at least one param leaf must really be feature-sharded across mp
    sharded = [l for l in jax.tree_util.tree_leaves(learner.train_state.params)
               if l.ndim >= 1
               and l.addressable_shards[0].data.shape[-1] != l.shape[-1]]
    assert sharded, "no param leaf sharded over mp"
    for leaf in jax.tree_util.tree_leaves(learner.train_state.params):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.slow
def test_end_to_end_device_placement_tensor_parallel(tmp_path):
    """VERDICT r3 #4: mesh.mp=2 with the DEFAULT device-replay placement —
    the fused sample-in-HBM step runs with wide params genuinely
    feature-sharded over mp (GSPMD) and replay dp-sharded, through the full
    orchestrator. Model sharding is a mesh-axis change on the flagship
    path."""
    cfg = tiny_config(tmp_path, **{
        "mesh.mp": 2, "mesh.dp": 2, "runtime.save_interval": 0})
    stacks = train(cfg, max_training_steps=6, max_seconds=300,
                   actor_mode="thread")
    learner = stacks[0].learner
    assert not learner.host_mode and learner.training_steps >= 6
    sharded = [l for l in jax.tree_util.tree_leaves(learner.train_state.params)
               if l.ndim >= 1
               and l.addressable_shards[0].data.shape[-1] != l.shape[-1]]
    assert sharded, "no param leaf sharded over mp"
    for leaf in jax.tree_util.tree_leaves(learner.train_state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    # replay stayed dp-sharded
    assert learner.replay_state.tree.sharding.spec[0] == "dp"


@pytest.mark.slow
def test_sigterm_maps_to_clean_stop(tmp_path):
    """An external SIGTERM lands on the stop-event path (wedge avoidance:
    TPU-holding runs must never be hard-killed mid-dispatch) and the previous
    handler is restored afterwards."""
    import signal
    import threading
    import time as time_mod

    cfg = tiny_config(tmp_path, **{"runtime.save_interval": 0})
    prev = signal.getsignal(signal.SIGTERM)
    timer = threading.Timer(
        2.0, lambda: os.kill(os.getpid(), signal.SIGTERM))
    timer.start()
    t0 = time_mod.time()
    try:
        train(cfg, max_training_steps=10**9, max_seconds=60.0,
              actor_mode="thread")
    finally:
        timer.cancel()
    assert time_mod.time() - t0 < 55.0, "signal did not stop the run"
    assert signal.getsignal(signal.SIGTERM) is prev


@pytest.mark.slow
def test_multi_step_dispatch_end_to_end(tmp_path):
    """steps_per_dispatch > 1 trains in K-step dispatches."""
    cfg = tiny_config(tmp_path, **{"runtime.steps_per_dispatch": 4,
                                   "runtime.save_interval": 0})
    stacks = train(cfg, max_training_steps=8, max_seconds=300,
                   actor_mode="thread")
    assert stacks[0].learner.training_steps in (8, 12)  # multiple of k=4
