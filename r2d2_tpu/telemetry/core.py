"""Telemetry facade: stage timers + span tracer + publication, one object
per process (the learner process shares one across its threads; each
spawned actor process builds its own bound to a TelemetryBoard slot).

Kill-switch: ``telemetry.enabled=false`` turns every entry point into a
cheap no-op (one attribute check); the module-level NULL_TELEMETRY serves
call sites that received no telemetry at all, so instrumented code never
branches on None. Overhead with telemetry ON is budgeted < 2% env-steps/s
(tools/e2e_bench.py --telemetry-ab measures it; PERF.md records the A/B).

``Telemetry.stage(name)`` is the one entry point of a timed block: the
stage histogram (where ``name`` is one of STAGES) and the nested span
(spans.py) from one pair of clock reads. ``observe``/``record_span`` stay
for sites that time themselves (host actors, serving, multihost).
"""

import json
import os
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from r2d2_tpu.telemetry.histogram import NBUCKETS, summarize
from r2d2_tpu.telemetry.spans import SpanTracer

# The canonical pipeline stages — ONE fixed, ordered list shared by local
# timers, the shm board layout, and the aggregated record, so counts merge
# elementwise everywhere. Actor-side stages are published through the
# board by process actors (thread actors observe straight into the
# learner's local timers); learner-side stages are always local.
STAGES = (
    "actor/env_step",             # venv/env .step per tick
    "actor/forward",              # jitted policy forward per tick
    "actor/block_emit",           # whole block sink call (incl. queue wait)
    "actor/queue_put",            # time inside put_patient (back-pressure)
    "actor/weight_sync",          # weight_poll + policy.update_params
    "actor/act_scan",             # fused on-device acting segment dispatch
    "ingest/ring_get",            # feeder drain: shm ring pop / queue get
    "ingest/stage",               # stager: stack + host->device + enqueue
    "ingest/commit",              # replay_add / add_many commit dispatch
    "learner/sample",             # host-placement prefetch sample
    "learner/train_dispatch",     # fused-step dispatch (host-side)
    "learner/device_sync",        # flush_metrics device readback
    "learner/priority_writeback", # host-placement async priority update
    "weights/publish",            # learner -> weight service publish
    "lockstep/dispatch",          # multihost: blocked in the psum collective
    "lockstep/step",              # multihost: one whole lockstep iteration
    "serve/enqueue",              # serving: request arrival -> dispatch
    "serve/batch_wait",           # serving: oldest request's fill wait
    "serve/forward",              # serving: jitted micro-batch forward
    "serve/reply",                # serving: state scatter + reply send
    "recovery/snapshot_capture",  # replay snapshot host cut (train path
                                  # cost; the write runs off-thread)
)
STAGE_INDEX: Dict[str, int] = {name: i for i, name in enumerate(STAGES)}


class StageTimers:
    """Per-process cumulative histogram matrix, (len(STAGES), NBUCKETS)
    int64. ``observe`` is the hot entry point: one bucket_index + one
    locked increment (stage cadence is per-tick at worst, so the lock is
    uncontended in practice; it exists because the stager, write-back,
    actor threads, and the main loop all observe into one matrix)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._m = np.zeros((len(STAGES), NBUCKETS), np.int64)
        self._prev = np.zeros_like(self._m)

    def observe(self, stage: str, seconds: float) -> None:
        from r2d2_tpu.telemetry.histogram import bucket_index
        row = STAGE_INDEX[stage]          # typo'd stage -> KeyError, loudly
        with self._lock:
            self._m[row, bucket_index(seconds)] += 1

    def cumulative(self) -> np.ndarray:
        with self._lock:
            return self._m.copy()

    def take(self) -> np.ndarray:
        """Counts observed since the previous take() -> (stages, buckets)."""
        with self._lock:
            cur = self._m.copy()
        delta = cur - self._prev
        self._prev = cur
        return delta


def summarize_matrix(matrix: np.ndarray) -> Dict[str, Dict[str, float]]:
    """{stage: {count, p50_ms, p95_ms, p99_ms}} for every stage with data."""
    out = {}
    for i, name in enumerate(STAGES):
        s = summarize(matrix[i])
        if s is not None:
            out[name] = s
    return out


class _Stage:
    """One timed block (``Telemetry.stage``): a ``with`` target that can
    take more tags before it closes (``tag``)."""

    __slots__ = ("_tele", "_name", "_iter", "_tags", "_span", "_t0")

    def __init__(self, tele: "Telemetry", name: str, iter: Any, tags: dict):
        self._tele, self._name, self._iter, self._tags = (tele, name, iter,
                                                          tags)
        self._span = None

    def tag(self, **tags) -> None:
        self._tags.update(tags)

    def __enter__(self) -> "_Stage":
        tracer = self._tele.spans
        if tracer.enabled:
            self._span = tracer.begin(self._name, self._iter, self._tags)
        else:
            self._t0 = time.time()
        return self

    def __exit__(self, *exc) -> None:
        if self._span is not None:
            seconds = self._tele.spans.end(self._span)
        else:
            seconds = time.time() - self._t0
        if self._name in STAGE_INDEX:
            self._tele.timers.observe(self._name, seconds)


class _NullStage:
    """What ``stage`` hands out when there is nothing to time."""

    __slots__ = ()

    def tag(self, **tags) -> None:
        pass

    def __enter__(self) -> "_NullStage":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_STAGE = _NullStage()


class Telemetry:
    """One per process. ``board``/``slot``: publication target for worker
    processes (the owner side instead passes the board to
    ``interval_summary`` via ``attach_board``)."""

    def __init__(self, enabled: bool = True, ring_size: int = 4096,
                 flush_interval_s: float = 5.0, spans: bool = True,
                 name: str = "main", board=None, slot: Optional[int] = None,
                 resource_gauges: bool = False):
        self.enabled = enabled
        self.name = name
        self.flush_interval_s = flush_interval_s
        self.timers = StageTimers()
        self.spans = SpanTracer(ring_size, enabled=enabled and spans)
        self._board = board          # worker side: publish target
        self._slot = slot
        # worker side (ISSUE 7): publish this process's RSS / cumulative
        # CPU into the board's gauge columns on the same flush cadence
        self._resource_gauges = resource_gauges
        self._agg_board = None       # owner side: aggregation source
        self._spans_path: Optional[str] = None
        self._drain_stop: Optional[threading.Event] = None
        self._drain_wake = threading.Event()
        self._drain_thread: Optional[threading.Thread] = None

    @classmethod
    def from_config(cls, cfg, name: str = "main", board=None,
                    slot: Optional[int] = None) -> "Telemetry":
        """Build from a Config (duck-typed: anything carrying a
        ``telemetry`` section with the TelemetryConfig fields)."""
        t = cfg.telemetry
        return cls(enabled=t.enabled, ring_size=t.ring_size,
                   flush_interval_s=t.flush_interval_s, spans=t.spans,
                   name=name, board=board, slot=slot,
                   resource_gauges=getattr(t, "resources_enabled", False))

    # -- hot-path entry points --

    def observe(self, stage: str, seconds: float) -> None:
        if self.enabled:
            self.timers.observe(stage, seconds)

    def record_span(self, name: str, t_start: float, t_end: float,
                    tags: Optional[dict] = None) -> None:
        self.spans.record(name, t_start, t_end, tags)

    def stage(self, name: str, *, iter: Any = None, **tags):
        """Time the ``with`` block once: into the stage histogram where
        ``name`` is one of STAGES, and as a span under the one open on
        this thread. A root names its ``iter`` (children inherit it).
        Disabled: one attribute check, no clock read; ``spans=false``
        alone: the histogram only."""
        if not self.enabled or not (self.spans.enabled
                                    or name in STAGE_INDEX):
            return _NULL_STAGE
        return _Stage(self, name, iter, tags)

    # -- publication / aggregation --

    def attach_board(self, board) -> None:
        """Owner side: fold this board's per-interval deltas into
        interval_summary() (the learner aggregating its actor fleet)."""
        self._agg_board = board

    def flush(self) -> None:
        """Publish cumulative counts to the board (worker side) and append
        drained spans to the spans file, if configured."""
        if not self.enabled:
            return
        if self._board is not None and self._slot is not None:
            self._board.publish(self._slot, self.timers.cumulative())
            if self._resource_gauges and hasattr(self._board,
                                                 "publish_gauges"):
                from r2d2_tpu.telemetry.resources import host_usage
                u = host_usage()
                self._board.publish_gauges(
                    self._slot, u["rss_bytes"] or 0,
                    int(u["cpu_s"] * 1e3))
        if self._spans_path:
            events = self.spans.drain()
            if events:
                with open(self._spans_path, "a") as f:
                    for ev in events:
                        ev["pid"] = self.name
                        f.write(json.dumps(ev) + "\n")

    def interval_summary(self) -> Dict[str, Dict[str, float]]:
        """The aggregated per-interval record: local observations since
        the last call, merged with the attached board's fleet-wide deltas.
        Consumes the interval — call once per log boundary."""
        if not self.enabled:
            return {}
        matrix = self.timers.take()
        if self._agg_board is not None:
            matrix = matrix + self._agg_board.take_deltas()
        return summarize_matrix(matrix)

    # -- background drain --

    def write_spans_to(self, spans_path: str, append: bool = False) -> None:
        """Make ``flush`` append drained spans to ``spans_path`` (JSONL).
        ``append=False`` truncates now (a fresh run's file);
        ``append=True`` keeps what's there — respawned actor processes
        and resumed runs must not wipe the history a post-mortem needs."""
        if not self.enabled or not self.spans.enabled:
            return
        os.makedirs(os.path.dirname(spans_path) or ".", exist_ok=True)
        if not append:
            open(spans_path, "w").close()
        self._spans_path = spans_path

    def start_drain(self, spans_path: Optional[str] = None,
                    append: bool = False) -> None:
        """Start the off-thread drain loop: every flush_interval_s,
        publish board counts and append spans to ``spans_path``
        (``write_spans_to``)."""
        if not self.enabled or self._drain_thread is not None:
            return
        if spans_path:
            self.write_spans_to(spans_path, append)
        self._drain_stop = threading.Event()

        def loop():
            while not self._drain_stop.is_set():
                self._drain_wake.wait(self.flush_interval_s)
                self._drain_wake.clear()
                try:
                    self.flush()
                except (OSError, ValueError):
                    # a torn-down board/file at shutdown must not kill the
                    # drain thread loudly; the final flush in close() is
                    # best-effort too
                    pass

        self._drain_thread = threading.Thread(
            target=loop, daemon=True, name=f"telemetry-drain-{self.name}")
        self._drain_thread.start()

    def flush_soon(self) -> None:
        """Write what the spans hold now: by the drain thread where one
        runs (a file has one writer), else here (``flush``)."""
        if self._drain_thread is not None:
            self._drain_wake.set()
        else:
            self.flush()

    def close(self) -> None:
        if self._drain_stop is not None:
            self._drain_stop.set()
            self._drain_wake.set()
            self._drain_thread.join(timeout=2.0)
            self._drain_thread = None
            self._drain_stop = None
        try:
            self.flush()
        except (OSError, ValueError):
            pass


NULL_TELEMETRY = Telemetry(enabled=False, spans=False, name="null")
