"""Low-overhead span tracer: thread-local ring buffers, drained off-thread.

Every pipeline stage worth seeing on a timeline records a
``(name, t_start, t_end, tags)`` event. The hot path takes NO locks: each
thread appends to its own bounded ``deque`` (the GIL makes ``append``
atomic; ``maxlen`` gives ring semantics — the oldest events fall off when
a drain falls behind, counted in ``dropped``). The Telemetry drain thread
(core.py) swaps events out periodically and appends them to a JSONL file
that ``tools/inspect.py`` turns into Chrome-trace JSON viewable in
Perfetto alongside an xprof capture.

Spans nest. ``begin``/``end`` (driven by ``Telemetry.stage``) keep a
thread-local stack of open spans: a span's ``parent`` is the one open on
its thread when it began, its ``iter`` the identifier its root was opened
with, and a parent's ``self`` is its duration less what its children
cover. ``record`` takes a span that is already over (timed by its caller)
and hangs it under the open one the same way; a listener that hears of a
span's start and end as they happen (the compile monitor's) passes their
times to ``begin`` and ``end``. While open, a span also holds a
``jax.profiler.TraceAnnotation``: with no capture live that is a flag
test, with one live the span stands on the host plane of the capture, on
the device operations' clock.

Span cadence is block-level (emits, drains, dispatches — a few to a few
hundred per second), NOT per-env-step: per-step timing goes to the
histograms (histogram.py), which cost one integer increment each.
"""

import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional


class OpenSpan:
    """A span that has begun on its thread and not ended."""

    __slots__ = ("name", "id", "iter", "tags", "t0", "covered", "annotation")


class SpanTracer:
    def __init__(self, ring_size: int = 4096, enabled: bool = True):
        self.ring_size = ring_size
        self.enabled = enabled
        self._local = threading.local()
        self._rings: List = []          # (thread_name, deque)
        self._register_lock = threading.Lock()   # registration only
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._annotate = None           # jax.profiler.TraceAnnotation, lazily
        self.dropped = 0                # approximate (racy increment is fine)

    def _thread(self):
        """This thread's ring and stack of open spans."""
        local = self._local
        if not hasattr(local, "ring"):
            local.ring = deque(maxlen=self.ring_size)
            local.stack = []
            with self._register_lock:
                self._rings.append((threading.current_thread(), local.ring))
        return local

    def _emit(self, local, name: str, t0: float, t1: float, tags, span_id,
              iter_id, covered: float = 0.0) -> float:
        """Row of a span that is over; the span still open on its thread,
        if any, is its parent. Returns the duration."""
        parent = local.stack[-1] if local.stack else None
        dur = t1 - t0
        if parent is not None:
            parent.covered += dur
        if len(local.ring) >= self.ring_size:
            self.dropped += 1
        local.ring.append((name, t0, t1, tags, span_id,
                           parent.id if parent else None, iter_id,
                           dur - covered))
        return dur

    def record(self, name: str, t_start: float, t_end: float,
               tags: Optional[Dict] = None) -> None:
        """Record one completed span (wall-clock unix seconds), as a
        child of the span open on this thread, if one is."""
        if not self.enabled:
            return
        local = self._thread()
        self._emit(local, name, t_start, t_end, tags, next(self._ids),
                   local.stack[-1].iter if local.stack else None)

    def begin(self, name: str, iter: Any = None,
              tags: Optional[Dict] = None,
              t0: Optional[float] = None) -> OpenSpan:
        """Open a span on this thread (callers check ``enabled``), at ``t0``
        or now. A root names its ``iter``; a child inherits its parent's."""
        if self._annotate is None:
            # not at import: the log tools read spans without loading jax
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation
        stack = self._thread().stack
        parent = stack[-1] if stack else None
        span = OpenSpan()
        span.name, span.tags, span.covered = name, tags, 0.0
        span.id = next(self._ids)
        span.iter = parent.iter if iter is None and parent else iter
        stack.append(span)
        meta = {"id": span.id}
        if parent is not None:
            meta["parent"] = parent.id
        if span.iter is not None:
            meta["iter"] = span.iter
        span.annotation = self._annotate(name, **meta)
        span.annotation.__enter__()
        span.t0 = time.time() if t0 is None else t0
        return span

    def end(self, span: OpenSpan, t1: Optional[float] = None) -> float:
        """Close ``span`` (the innermost open on this thread), at ``t1`` or
        now, and record it; returns its duration."""
        if t1 is None:
            t1 = time.time()
        span.annotation.__exit__(None, None, None)
        local = self._local
        local.stack.pop()
        return self._emit(local, span.name, span.t0, t1, span.tags or None,
                          span.id, span.iter, span.covered)

    def drain(self) -> List[dict]:
        """Pop every buffered event from every thread's ring (off-thread:
        the drain loop owns this). Writers keep appending concurrently;
        ``popleft`` and ``append`` never touch the same end."""
        out = []
        with self._register_lock:
            rings = list(self._rings)
        dead = []
        for thread, ring in rings:
            for _ in range(len(ring)):
                try:
                    (name, t0, t1, tags, span_id, parent, iter_id,
                     self_s) = ring.popleft()
                except IndexError:
                    break
                ev = {"name": name, "ts": t0, "dur": t1 - t0,
                      "tid": thread.name, "id": span_id, "parent": parent,
                      "iter": iter_id, "self": self_s}
                if tags:
                    ev["tags"] = tags
                out.append(ev)
            if not thread.is_alive() and not ring:
                # respawned workers register fresh rings; drained rings of
                # dead threads must not accumulate over a crash-looping
                # soak
                dead.append((thread, ring))
        if dead:
            with self._register_lock:
                for entry in dead:
                    try:
                        self._rings.remove(entry)
                    except ValueError:
                        pass
        out.sort(key=lambda e: e["ts"])
        return out


def chrome_trace_events(events: List[dict], pid: str,
                        pid_index: int = 0) -> List[dict]:
    """Convert drained span events (JSONL schema above) to Chrome-trace
    'X' events plus the process/thread name metadata Perfetto uses for
    track labels. Timestamps convert to microseconds."""
    tids: Dict[str, int] = {}
    out = [{"ph": "M", "name": "process_name", "pid": pid_index,
            "args": {"name": pid}}]
    for ev in events:
        tid = tids.setdefault(ev.get("tid", "main"), len(tids))
        # the tree rides in args, so Perfetto shows it from the JSONL alone
        tree = {k: ev[k] for k in ("id", "parent", "iter")
                if ev.get(k) is not None}
        out.append({"ph": "X", "name": ev["name"], "pid": pid_index,
                    "tid": tid, "ts": round(ev["ts"] * 1e6, 1),
                    "dur": round(ev["dur"] * 1e6, 1),
                    "args": {**(ev.get("tags") or {}), **tree}})
    for name, tid in tids.items():
        out.append({"ph": "M", "name": "thread_name", "pid": pid_index,
                    "tid": tid, "args": {"name": name}})
    return out
