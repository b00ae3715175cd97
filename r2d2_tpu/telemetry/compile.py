"""XLA compilation telemetry (ISSUE 7): compile counts/wall-time and
post-warm-up retrace detection.

Recompiles are this stack's quietest failure mode: a jitted function
handed a new abstract shape silently recompiles (~1.5 s each on the CPU
container, tens of seconds for the fused train program), and the PR-2 ingestion saga
showed a single lazy mid-run ``replay_add_many`` compile backing the
feeder up enough to park the whole actor fleet. Nothing surfaced it —
the symptom was a throughput dip a human had to correlate by hand.

Two capture channels, both public-ish and cheap:

  * ``jax.monitoring`` duration events
    (``/jax/core/compile/backend_compile_duration``): every backend
    compile's wall time, no function identity — the aggregate
    count/time counters.
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

Given a ``Telemetry``, every backend compile also records a ``compile``
span (tag ``fn``: the name the log line gave on that thread just before)
under whatever stage is open on the compiling thread, so a build inside
a measured window names its iteration and its call.

One monitor per process (module-level active slot): jax.monitoring has
no per-listener unregister, so ONE dispatching listener is registered on
first install and routes to whichever monitor is active.
"""

import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional

_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"
_PXLA_LOGGER = "jax._src.interpreters.pxla"
# "Compiling <name> with global shapes and types [<avals>]. Argument ..."
_COMPILING_RE = re.compile(
    r"Compiling ([^\s]+) (?:with global shapes and types |for pjit )?"
    r"\[?(.*?)\]?\.? Argument", re.DOTALL)

_ACTIVE: Optional["CompileMonitor"] = None
_LISTENER_REGISTERED = False
# reentrant: install() displaces a previous owner by calling ITS
# uninstall() while already holding the lock
_INSTALL_LOCK = threading.RLock()


def _duration_listener(event: str, duration: float, **kwargs) -> None:
    mon = _ACTIVE
    if mon is not None and event == _COMPILE_DURATION_EVENT:
        mon._on_backend_compile(duration)


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
    """The process's currently-installed monitor, or None. Orchestrating
    loops check this before installing: compile events are process-global,
    so the FIRST stack in a multiplayer process owns the monitor and later
    stacks must not displace it (install() deactivates the previous
    owner)."""
    return _ACTIVE


class CompileMonitor:
    """Per-process compile/retrace tracker. ``install()`` activates the
    capture channels; ``uninstall()`` restores the logger exactly (tests
    install/uninstall repeatedly). Counters are cumulative; the record
    block reads per-interval deltas via :meth:`interval_summary`."""

    MAX_RETRACE_LOG = 32      # retained retrace events (newest kept)

    def __init__(self, telemetry=None):
        self._telemetry = telemetry    # where ``compile`` spans go
        self._compiling = threading.local()   # .fn: the pxla line's name
        self._lock = threading.Lock()
        self.compiles = 0              # backend compiles (monitoring event)
        self.compile_time_s = 0.0
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

    def _on_backend_compile(self, duration: float) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_time_s += float(duration)
        if self._telemetry is not None:
            # the listener runs on the compiling thread, as the build ends
            now = time.time()
            self._telemetry.record_span(
                "compile", now - duration, now,
                {"fn": getattr(self._compiling, "fn", None)})

    def _on_compile(self, name: str, avals: str) -> None:
        self._compiling.fn = name
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
        global _ACTIVE, _LISTENER_REGISTERED
        with _INSTALL_LOCK:
            if _ACTIVE is self:
                return self
            if _ACTIVE is not None:
                _ACTIVE.uninstall()
            if not _LISTENER_REGISTERED:
                import jax.monitoring
                jax.monitoring.register_event_duration_secs_listener(
                    _duration_listener)
                _LISTENER_REGISTERED = True
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
            }
            if self._retrace_log:
                out["last_retrace"] = dict(self._retrace_log[-1])
            return out

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
