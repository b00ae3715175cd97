"""From a profiler capture (``*.xplane.pb``) to the numbers the per-layer
metrics read. Nothing here knows a cell or a metric.

What a TPU capture holds (jax 0.9.0, libtpu 0.0.34; ``dump.py`` shows it):
one plane ``/device:TPU:<n>`` per chip with a line ``XLA Modules`` (one event
per program execution, named ``jit_<function>(<program id>)``) and a line
``XLA Ops`` (one event per HLO operation the core executed, named by the
instruction's text; a ``while``, a ``conditional`` and every other operation
that calls a computation encloses the events of what it calls; asynchronous
copies run beside it on ``Async XLA Ops``, which is not read), and one plane
``/host:CPU`` whose thread lines carry the ``TraceAnnotation`` events of the
benchmark. All are on one clock, in ns from the start of the capture. An
operation's scope path (the ``jax.named_scope`` and flax module names of the
code that made it) is not in its event: ``hlo_names.py`` takes it from the
program's HLO, which the capture keeps.

  * self time: an operation's duration minus that of the operations it
    encloses on its line, so a parent is never counted with its child and
    the self times of a line add up to the time the line was busy;
  * busy: the union of the operation intervals of a device inside the traced
    window; idle share: 1 - busy / window;
  * gaps: the idle intervals, each laid to the host spans that overlap it
    (the benchmark's annotations, and spans the program wrote on the wall
    clock, moved onto the trace's clock by the ``bench_clock`` marker).

Every operation keeps its scope path as the HLO gives it (``Op.path``), and
programs and kernels are found by a token in it (``module_runs``,
``kernel_calls``, ``self_under_s``), so a reader matches the scope it is
about and needs no entry here. To split the busy time into rows that add up
(``self_by_scope``, ``breakdown``) each operation is also given one scope by
the configuration's scope table, a data file under ``scopes/`` that the
configuration names: rows of (token, scope), the first row whose token is in
the path wins, so an inner scope stands before the scopes it nests in.
"""

import collections
import dataclasses
import re
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from benchmarks.harness import (MARK_CLOCK, MARK_WINDOW_BEGIN,
                                MARK_WINDOW_END)
from benchmarks.trace import hlo_names

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

ScopeTable = Sequence[Tuple[str, str]]        # (token in the path, scope)
UNATTRIBUTED = "unattributed"
NO_HOST_SPAN = "(no host span)"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel

Interval = Tuple[float, float]


def scope_of(path: str, table: ScopeTable) -> str:
    for token, scope in table:
        if token in path:
            return scope
    return UNATTRIBUTED


@dataclasses.dataclass
class Op:
    name: str             # the instruction's name, ``fusion.12``
    path: str             # its scope path in the HLO; "" where it has none
    scope: str            # what the scope table makes of the path
    start: float          # ns on the trace's clock
    end: float
    kernel: bool = False  # a Pallas kernel's call
    self_ns: float = 0.0

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ModuleRun:
    name: str
    start: float
    end: float
    paths: Set[str] = dataclasses.field(default_factory=set)  # of its ops

    @property
    def dur(self) -> float:
        return self.end - self.start

    def holds(self, token: str) -> bool:
        return any(token in path for path in self.paths)


@dataclasses.dataclass
class Device:
    ordinal: int
    ops: List[Op]                  # ``summarize_data`` keeps the window's
    modules: List[ModuleRun]
    busy: List[Interval]           # merged, clipped to the window

    def busy_ns(self) -> float:
        return sum(b - a for a, b in self.busy)


def self_times(ops: List[Op]) -> None:
    """Set ``self_ns`` on operations of one line, which nest by time: a
    parent's self time is its duration minus its direct children's. Sorts
    ``ops`` by start, a parent before its children."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.dur
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent.self_ns -= min(op.end, parent.end) - op.start
        stack.append(op)
    for op in ops:
        op.self_ns = max(op.self_ns, 0.0)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _subtract(span: Interval, covered: List[Interval]) -> List[Interval]:
    out, (a, b) = [], span
    for c, d in covered:
        if d <= a or c >= b:
            continue
        if c > a:
            out.append((a, c))
        a = max(a, d)
        if a >= b:
            break
    if a < b:
        out.append((a, b))
    return out


def lay_gaps_to_spans(idle: Sequence[Interval],
                      spans: Sequence[Tuple[str, float, float]]
                      ) -> Dict[str, float]:
    """Idle nanoseconds by the host span that covers them; where spans nest
    the innermost (shortest) takes its part first."""
    by_name: Dict[str, float] = collections.defaultdict(float)
    ordered = sorted(spans, key=lambda s: s[2] - s[1])
    for lo, hi in idle:
        covered: List[Interval] = []
        for name, a, b in ordered:
            if b <= lo or a >= hi:
                continue
            for piece in _subtract((max(a, lo), min(b, hi)), covered):
                by_name[name] += piece[1] - piece[0]
                covered = merge(covered + [piece])
        left = (hi - lo) - sum(d - c for c, d in covered)
        if left > 0:
            by_name[NO_HOST_SPAN] += left
    return dict(by_name)


def _stats(event) -> Dict[str, object]:
    return {key: value for key, value in event.stats}


@dataclasses.dataclass
class TraceSummary:
    devices: List[Device]
    window: Interval                              # ns
    host_spans: List[Tuple[str, float, float]]    # name, start, end (ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Averaged over the devices."""
        return sum(d.busy_ns() for d in self.devices) / len(self.devices) / 1e9

    def device_window(self) -> Dict[str, float]:
        return {"busy_s": self.busy_s(), "window_s": self.window_s}

    def idle_share(self) -> float:
        """Of the idlest device."""
        width = self.window[1] - self.window[0]
        return max(1.0 - d.busy_ns() / width for d in self.devices)

    def self_by_scope(self) -> Dict[str, float]:
        """Seconds of self time by scope inside the window, averaged over
        the devices; ``unattributed`` is a row like any other."""
        total: Dict[str, float] = collections.defaultdict(float)
        for d in self.devices:
            for o in d.ops:
                total[o.scope] += o.self_ns
        return {k: v / len(self.devices) / 1e9 for k, v in total.items()}

    def self_total_s(self) -> float:
        return sum(self.self_by_scope().values())

    def self_under_s(self, token: str) -> float:
        """Seconds of self time, averaged over the devices, of the
        operations whose scope path holds ``token``, whichever row of
        ``self_by_scope`` they are counted in."""
        return sum(o.self_ns for d in self.devices for o in d.ops
                   if token in o.path) / len(self.devices) / 1e9

    def module_runs(self, token: str, without: Sequence[str] = ()
                    ) -> List[ModuleRun]:
        """Executions, on the first device and inside the window, of the
        programs that hold operations under ``token`` and none under
        ``without``."""
        lo, hi = self.window
        return [m for m in self.devices[0].modules
                if m.start >= lo and m.end <= hi and m.holds(token)
                and not any(m.holds(w) for w in without)]

    def kernel_calls(self, token: str) -> List[Op]:
        """Calls of Pallas kernels under ``token`` on the first device,
        inside the window."""
        return [o for o in self.devices[0].ops
                if o.kernel and token in o.path]

    def collective_self_s(self) -> float:
        """Self time of collective operations on the operation line, which
        runs one operation at a time: while a collective is the running leaf
        no compute is. Of the device that waits longest."""
        return max(sum(o.self_ns for o in d.ops
                       if COLLECTIVE.match(o.name))
                   for d in self.devices) / 1e9

    def idle_by_host_span(self) -> Dict[str, float]:
        """Idle seconds of the idlest device by host span."""
        width = self.window[1] - self.window[0]
        worst = max(self.devices, key=lambda d: 1.0 - d.busy_ns() / width)
        laid = lay_gaps_to_spans(gaps(worst.busy, *self.window),
                                 self.host_spans)
        return {k: v / 1e9 for k, v in laid.items()}

    def breakdown(self) -> Dict[str, List[List]]:
        """The contract's ``breakdown``: self seconds by scope and operation
        (the ten largest; averaged over the devices), and idle seconds by
        host span."""
        by_op: Dict[str, float] = collections.defaultdict(float)
        for d in self.devices:
            for o in d.ops:
                kind = re.sub(r"[.\d]+$", "", o.name)
                by_op[f"{o.scope}:{kind}"] += o.self_ns
        n = len(self.devices) * 1e9
        ops = sorted(((k, v / n) for k, v in by_op.items()),
                     key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_host_span().items(),
                      key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _device(plane, ordinal: int, program_scopes: Dict[str, Dict[str, str]],
            table: ScopeTable) -> Optional[Device]:
    lines = {line.name: line for line in plane.lines}
    if OP_LINE not in lines:
        return None
    modules = sorted((ModuleRun(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines[MODULE_LINE].events),
                     key=lambda m: m.start) if MODULE_LINE in lines else []
    events = sorted(lines[OP_LINE].events, key=lambda e: e.start_ns)
    ops: List[Op] = []
    i = 0
    for e in events:
        while i < len(modules) and modules[i].end <= e.start_ns:
            i += 1
        module = (modules[i] if i < len(modules)
                  and modules[i].start <= e.start_ns else None)
        name = hlo_names.instruction_name(e.name)
        stats = _stats(e)
        # the scope path: from the program's HLO in a capture file, from the
        # event's own stat in a capture cut down to text (xspace_text.py)
        path = stats.get("op_name") or program_scopes.get(
            module.name if module else "", {}).get(name, "")
        ops.append(Op(name, path, scope_of(path, table), e.start_ns,
                      e.start_ns + e.duration_ns,
                      kernel=KERNEL_TARGET in e.name or bool(
                          stats.get("kernel"))))
        if module is not None:
            module.paths.add(path)
    if not ops:
        return None
    self_times(ops)
    return Device(ordinal, ops, modules, [])


def summarize(path: str, **kwargs) -> Optional["TraceSummary"]:
    """``summarize_data`` of the capture file at ``path``, with the scope
    paths its programs' HLO gives."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return summarize_data(ProfileData.from_serialized_xspace(raw),
                          program_scopes=hlo_names.program_scopes(raw),
                          **kwargs)


def summarize_data(data, scopes: ScopeTable = (),
                   program_scopes: Optional[Dict[str, Dict[str, str]]] = None,
                   host_names: Optional[Sequence[str]] = None,
                   external_spans: Optional[Sequence[Tuple[str, float, float]]]
                   = None, chips: Optional[int] = None
                   ) -> Optional[TraceSummary]:
    """Reduce one capture (a ``ProfileData``). ``scopes`` is the
    configuration's scope table (without one every operation is
    ``unattributed``); ``host_names`` are the names of the benchmark's own
    host spans (others on the host plane are the runtime's and are left
    out); ``external_spans`` are (name, start, end) on the wall clock
    (``time.time()``, seconds). None when the capture holds no device
    operation (a CPU run)."""
    keep = set(host_names or ())
    with warnings.catch_warnings():
        # iterating an event's stats warns about jaxlib's own binding type
        warnings.filterwarnings("ignore", "builtin type event_stats",
                                DeprecationWarning)
        planes = list(data.planes)
        devices = [dev for dev in (
            _device(p, int(DEVICE_PLANE.match(p.name).group(1)),
                    program_scopes or {}, scopes)
            for p in planes if DEVICE_PLANE.match(p.name)) if dev is not None]
        host_events = [(e.name, e.start_ns, e.duration_ns, _stats(e))
                       for p in planes if p.name == HOST_PLANE
                       for line in p.lines for e in line.events]
    if not devices:
        return None
    marks: Dict[str, float] = {}
    clock_offset: Optional[float] = None
    host_spans: List[Tuple[str, float, float]] = []
    for name, start, dur, stats in host_events:
        if name == MARK_CLOCK:
            clock_offset = start - stats["unix_ns"]
        elif name in (MARK_WINDOW_BEGIN, MARK_WINDOW_END):
            marks[name] = start
        elif name in keep:
            host_spans.append((name, start, start + dur))
    devices.sort(key=lambda d: d.ordinal)
    if chips is not None:
        devices = devices[:chips]
    lo = marks.get(MARK_WINDOW_BEGIN,
                   min(o.start for d in devices for o in d.ops))
    hi = marks.get(MARK_WINDOW_END,
                   max(o.end for d in devices for o in d.ops))
    for d in devices:
        d.busy = clip(merge((o.start, o.end) for o in d.ops), lo, hi)
        d.ops = [o for o in d.ops if o.start >= lo and o.end <= hi]
    if external_spans and clock_offset is not None:
        host_spans += [(n, a * 1e9 + clock_offset, b * 1e9 + clock_offset)
                       for n, a, b in external_spans]
    return TraceSummary(devices, (lo, hi), host_spans)
