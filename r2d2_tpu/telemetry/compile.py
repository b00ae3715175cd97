"""XLA compilation telemetry (ISSUE 7): compile counts/wall-time, the
persistent cache's hits and misses, each build's phases as spans (PR 38)
and post-warm-up retrace detection.

Recompiles are this stack's quietest failure mode: a jitted function
handed a new abstract shape silently recompiles (~1.5 s each on the CPU
container, tens of seconds for the fused train program), and the PR-2 ingestion saga
showed a single lazy mid-run ``replay_add_many`` compile backing the
feeder up enough to park the whole actor fleet. Nothing surfaced it —
the symptom was a throughput dip a human had to correlate by hand.

Two capture channels, both public and cheap:

  * ``jax.monitoring``'s compile events, each with ``fun_name``: tracing
    to a jaxpr (``/jax/core/compile/jaxpr_trace_duration``), lowering to
    MLIR (``jaxpr_to_mlir_module_duration``) and XLA's build, or the
    persistent cache's read and load of the executable
    (``backend_compile_duration``). Each phase is announced as it starts
    (a scalar event whose value is its start on ``time.time()``) and
    reported as it ends (a time-span event). The cache's events
    (``compile_requests_use_cache``, ``cache_hits``,
    ``cache_retrieval_time_sec``) fire on the compiling thread inside the
    backend phase.
  * the ``jax._src.interpreters.pxla`` DEBUG log line
    ``"Compiling <fn> with global shapes and types [avals]"``: function
    NAME + ABSTRACT SHAPES per compile. The monitor attaches a logging
    handler at DEBUG and stops propagation (restored at uninstall) so
    capture costs no stderr spam; WARNING+ records are re-emitted to the
    parent so real jax warnings stay visible.

Retrace = a compile AFTER :meth:`CompileMonitor.mark_warm` of a function
name seen before with a DIFFERENT aval signature — exactly the
"same fn, new shapes" event that parks actors. Flagged with the
offending avals in the record's ``resources.compile`` block, and counted
per interval so the sentinel's ``retrace_storm`` rule can fire on a
burst. Late FIRST compiles (a new function after warm-up, e.g. an
odd-size stager bucket) count as ``late_compiles`` — noteworthy, but not
a retrace.

Given a ``Telemetry``, each phase is a span (PR 38): ``compile/trace``,
``compile/lower`` and ``compile/backend``, tagged ``fn``; the backend's
also ``cache`` (``hit`` | ``miss`` | ``off``: the cache was not asked)
and, on a hit, ``cache_read_s``. A span opens at the phase's start under
the stage open on the compiling thread and closes at its end, so a build
names its iteration and its call, and what runs inside a phase (an eager
op's build while tracing) hangs under it. A trace or lowering inside
another (each jnp function a traced function calls is a jit of its own:
~1,700 in a tiny train step) is part of the outer span and records none:
the spans of trace and lowering on a thread are their union
(``trace_lower_s``).

One monitor per process (module-level active slot): the listeners are
registered once, on first install, and route to whichever monitor is
active.
"""

import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_SPAN_OF = {_TRACE: "compile/trace", _LOWER: "compile/lower",
            _BACKEND: "compile/backend"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_PXLA_LOGGER = "jax._src.interpreters.pxla"
# "Compiling <name> with global shapes and types [<avals>]. Argument ..."
_COMPILING_RE = re.compile(
    r"Compiling ([^\s]+) (?:with global shapes and types |for pjit )?"
    r"\[?(.*?)\]?\.? Argument", re.DOTALL)

_ACTIVE: Optional["CompileMonitor"] = None
_LISTENERS_REGISTERED = False
# reentrant: install() displaces a previous owner by calling ITS
# uninstall() while already holding the lock
_INSTALL_LOCK = threading.RLock()


def _phase_start(event: str, value: float, **kwargs) -> None:
    mon = _ACTIVE
    if mon is not None and event in _SPAN_OF:
        mon._on_phase_start(event, value, kwargs.get("fun_name"))


def _phase_end(event: str, start: float, end: float, **kwargs) -> None:
    mon = _ACTIVE
    if mon is not None and event in _SPAN_OF:
        mon._on_phase_end(event, start, end)


def _cache_event(event: str, **kwargs) -> None:
    mon = _ACTIVE
    if mon is not None and event in (_CACHE_ASKED, _CACHE_HIT):
        mon._on_cache("hit" if event == _CACHE_HIT else "miss")


def _cache_read(event: str, duration: float, **kwargs) -> None:
    mon = _ACTIVE
    if mon is not None and event == _CACHE_READ:
        mon._on_cache("hit", read_s=duration)


def _inside_trace(phases: list) -> bool:
    """A trace or lowering is open on the thread: one that begins now is
    part of it."""
    return any(event != _BACKEND for event, _, _ in phases)


class _CompileLogHandler(logging.Handler):
    """Captures the pxla compile lines for the active monitor; WARNING+
    records pass through to the 'jax' parent handler so suppressing
    propagation (needed to keep DEBUG capture off stderr) loses
    nothing user-visible."""

    def emit(self, record: logging.LogRecord) -> None:
        mon = _ACTIVE
        if mon is not None:
            try:
                msg = record.getMessage()
            except Exception:
                return
            m = _COMPILING_RE.search(msg)
            if m is not None:
                mon._on_compile(m.group(1), m.group(2))
        if record.levelno >= logging.WARNING:
            logging.getLogger("jax").handle(record)


def active_monitor() -> Optional["CompileMonitor"]:
    """The process's currently-installed monitor, or None. The first
    ``Learner`` of a process installs one bound to its Telemetry and the
    loop around it takes that one over; compile events are
    process-global, so later stacks must not displace it (install()
    deactivates the previous owner)."""
    return _ACTIVE


class CompileMonitor:
    """Per-process compile/retrace tracker. ``install()`` activates the
    capture channels; ``uninstall()`` restores the logger exactly (tests
    install/uninstall repeatedly). Counters are cumulative; the record
    block reads per-interval deltas via :meth:`interval_summary`."""

    MAX_RETRACE_LOG = 32      # retained retrace events (newest kept)

    def __init__(self, telemetry=None):
        self._telemetry = telemetry    # where the phases' spans go
        self._local = threading.local()   # .phases: begun, not ended
        self._lock = threading.Lock()
        self.compiles = 0              # backend phases (monitoring event)
        self.compile_time_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.trace_lower_s = 0.0       # union of trace + lowering a thread
        self.traced_compiles = 0       # named compiles (pxla log line)
        self.retraces = 0
        self.late_compiles = 0         # post-warm first compile of a new fn
        self.warm = False
        self._signatures: Dict[str, set] = {}
        self._retrace_log: List[dict] = []
        self._prev = (0, 0.0, 0, 0)    # interval take baseline
        self._handler: Optional[_CompileLogHandler] = None
        self._saved_logger_state: Optional[tuple] = None

    # -- capture-channel callbacks --

    def _phases(self) -> list:
        """This thread's phases begun and not ended, innermost last:
        ``(event, open span or None, tags)``."""
        local = self._local
        if not hasattr(local, "phases"):
            local.phases = []
        return local.phases

    def _on_phase_start(self, event: str, t0: float, fn: str) -> None:
        phases = self._phases()
        tags = {"fn": fn}
        span = None
        tele = self._telemetry
        if (tele is not None and tele.spans.enabled
                and (event == _BACKEND or not _inside_trace(phases))):
            span = tele.spans.begin(_SPAN_OF[event], tags=tags, t0=t0)
        phases.append((event, span, tags))

    def _on_phase_end(self, event: str, t0: float, t1: float) -> None:
        phases = self._phases()
        if not phases or phases[-1][0] != event:
            return          # began before this monitor was installed
        _, span, tags = phases.pop()
        if event == _BACKEND:
            tags.setdefault("cache", "off")
            self._on_backend_compile(t1 - t0, tags["cache"])
        elif not _inside_trace(phases):
            with self._lock:
                self.trace_lower_s += t1 - t0
        if span is not None:
            self._telemetry.spans.end(span, t1)

    def _on_cache(self, state: str, read_s: Optional[float] = None) -> None:
        phases = self._phases()
        if phases and phases[-1][0] == _BACKEND:
            tags = phases[-1][2]
            if tags.get("cache") != "hit":
                tags["cache"] = state
            if read_s is not None:
                tags["cache_read_s"] = read_s

    def _on_backend_compile(self, duration: float,
                            cache: Optional[str] = None) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_time_s += float(duration)
            if cache == "hit":
                self.cache_hits += 1
            elif cache == "miss":
                self.cache_misses += 1

    def _on_compile(self, name: str, avals: str) -> None:
        with self._lock:
            self.traced_compiles += 1
            seen = self._signatures.setdefault(name, set())
            is_retrace = self.warm and bool(seen) and avals not in seen
            if self.warm and not seen:
                self.late_compiles += 1
            seen.add(avals)
            if is_retrace:
                self.retraces += 1
                self._retrace_log.append(
                    {"fn": name, "avals": avals[:400], "t": time.time()})
                del self._retrace_log[:-self.MAX_RETRACE_LOG]

    # -- lifecycle --

    def install(self) -> "CompileMonitor":
        global _ACTIVE, _LISTENERS_REGISTERED
        with _INSTALL_LOCK:
            if _ACTIVE is self:
                return self
            if _ACTIVE is not None:
                _ACTIVE.uninstall()
            if not _LISTENERS_REGISTERED:
                import jax.monitoring as m
                m.register_scalar_listener(_phase_start)
                m.register_event_time_span_listener(_phase_end)
                m.register_event_listener(_cache_event)
                m.register_event_duration_secs_listener(_cache_read)
                _LISTENERS_REGISTERED = True
            logger = logging.getLogger(_PXLA_LOGGER)
            self._saved_logger_state = (logger.level, logger.propagate)
            self._handler = _CompileLogHandler(level=logging.DEBUG)
            logger.addHandler(self._handler)
            logger.setLevel(logging.DEBUG)
            # propagation off: the 'jax' parent has a stderr handler that
            # would print every DEBUG compile line; the handler re-emits
            # WARNING+ records there itself
            logger.propagate = False
            _ACTIVE = self
        return self

    def uninstall(self) -> None:
        global _ACTIVE
        with _INSTALL_LOCK:
            if _ACTIVE is not self:
                return
            logger = logging.getLogger(_PXLA_LOGGER)
            if self._handler is not None:
                logger.removeHandler(self._handler)
                self._handler = None
            if self._saved_logger_state is not None:
                logger.setLevel(self._saved_logger_state[0])
                logger.propagate = self._saved_logger_state[1]
                self._saved_logger_state = None
            _ACTIVE = None

    def mark_warm(self) -> None:
        """Declare warm-up over: every fn compiled so far is baseline;
        further compiles of known fns with new avals are retraces.
        Idempotent — call it at the first log boundary where training has
        started (the train program has compiled by then)."""
        with self._lock:
            self.warm = True

    # -- reads --

    def totals(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                "compiles_total": self.compiles,
                "compile_time_s_total": round(self.compile_time_s, 3),
                "retraces_total": self.retraces,
                "late_compiles": self.late_compiles,
                "warm": self.warm,
                **self._set_up(),
            }
            if self._retrace_log:
                out["last_retrace"] = dict(self._retrace_log[-1])
            return out

    def interval_summary(self) -> Dict[str, Any]:
        """totals() plus per-interval deltas (consumes the interval) —
        the record's ``resources.compile`` block; ``retraces_interval``
        is what the retrace_storm alert rule reads."""
        with self._lock:
            cur = (self.compiles, self.compile_time_s, self.retraces,
                   self.late_compiles)
            pc, pt, pr, pl = self._prev
            self._prev = cur
            out = {
                "compiles": cur[0] - pc,
                "compile_time_s": round(cur[1] - pt, 3),
                "retraces_interval": cur[2] - pr,
                "late_compiles_interval": cur[3] - pl,
                "compiles_total": cur[0],
                "compile_time_s_total": round(cur[1], 3),
                "retraces_total": cur[2],
                "late_compiles": cur[3],
                "warm": self.warm,
                **self._set_up(),
            }
            if self._retrace_log:
                out["last_retrace"] = dict(self._retrace_log[-1])
            return out

    def _set_up(self) -> Dict[str, Any]:
        """What the builds cost so far (callers hold the lock): the
        cache's answers, trace + lowering (their union on each thread)
        and the backend phases."""
        return {"cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "trace_lower_s": round(self.trace_lower_s, 3),
                "backend_s": round(self.compile_time_s, 3)}

    def functions_seen(self) -> Dict[str, int]:
        """{fn name: distinct aval signatures} — the tracked universe."""
        with self._lock:
            return {k: len(v) for k, v in self._signatures.items()}


def aot_coverage(expected: List[int], compiled: List[int]) -> dict:
    """AOT-precompile coverage report (the stager's pow2 add_many
    buckets): which batch sizes have executables vs which would compile
    lazily mid-run — the exact hazard the PR-2 precompile exists to
    prevent; a non-empty ``missing`` list is the regression signal."""
    expected = sorted(set(int(x) for x in expected))
    compiled = sorted(set(int(x) for x in compiled))
    return {"expected": expected, "compiled": compiled,
            "missing": [s for s in expected if s not in compiled],
            "extra": [s for s in compiled if s not in expected]}
