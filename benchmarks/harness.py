"""What every cell shares: finding a cell's files by name, building the
program's ``Config`` from them, and the host-side instruments (compile
counter, host spans, trace control, device facts)."""

import contextlib
import importlib
import json
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
# what a run leaves behind (program logs, spans, the trace); git-ignored
OUT_DIR = os.path.join(ROOT, ".bench")

# markers the trace reduction keys on (benchmarks/trace/reduce.py)
MARK_CLOCK = "bench_clock"
MARK_WINDOW_BEGIN = "bench_window_begin"
MARK_WINDOW_END = "bench_window_end"


class BenchError(Exception):
    """The benchmark's files do not fit together (a name without its file, a
    traffic mix that overrides a size); the message says which."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json (known: "
                     f"{[c['name'] for c in bench['workloads']]})")


def cell_metrics(bench: dict, cell_name: str, level: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell: all
    of them, except those that list the cells they exist in. A per-layer
    metric is kept only where the end-to-end metric it moves is."""
    def applies(m: dict) -> bool:
        return "workloads" not in m or cell_name in m["workloads"]

    if level == "end_to_end":
        return [m for m in bench["end_to_end"] if applies(m)]
    e2e = {m["name"] for m in cell_metrics(bench, cell_name, "end_to_end")}
    return [m for m in bench["per_layer"] if applies(m) and m["moves"] in e2e]


def config_doc(bench: dict, config_name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == config_name:
            return load_json(os.path.join(root, c["file"]))
    raise BenchError(f"no config {config_name!r} in BENCHMARK.json")


def traffic_doc(traffic_name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, "benchmarks", "workloads",
                        f"{traffic_name}.json")
    if not os.path.exists(path):
        raise BenchError(f"traffic mix {traffic_name!r} has no file {path}")
    return load_json(path)


def scope_table(config: dict, root: str = ROOT) -> List[Tuple[str, str]]:
    """The rows a configuration's busy time is split into: the (token,
    scope) pairs of ``benchmarks/trace/scopes/<name>.json``, named by the
    configuration's ``scopes`` (``benchmarks/trace/reduce.py`` applies it)."""
    name = config["scopes"]
    path = os.path.join(root, "benchmarks", "trace", "scopes", f"{name}.json")
    if not os.path.exists(path):
        raise BenchError(f"scope table {name!r} has no file {path}")
    return [(token, scope) for token, scope in load_json(path)["scopes"]]


def _hashable(value: Any) -> Any:
    """JSON lists become tuples: the program's config sections are frozen
    dataclasses that flax hashes (``network.conv_layers``)."""
    if isinstance(value, list):
        return tuple(_hashable(v) for v in value)
    return value


def program_overrides(config: dict, traffic: dict,
                      rehearse: bool = False) -> Dict[str, Any]:
    """Dotted ``Config`` overrides of one cell: the configuration's sizes,
    then the traffic mix's path and run control. A traffic mix may not set a
    key its configuration sets — sizes belong to the configuration alone.
    ``rehearse`` adds each file's tiny CPU twin on top."""
    sizes = dict(config.get("overrides", {}))
    path = dict(traffic.get("overrides", {}))
    clash = sorted(set(sizes) & set(path))
    if clash:
        raise BenchError(f"traffic mix overrides configuration sizes: {clash}")
    merged = {**sizes, **path}
    if rehearse:
        merged.update(config.get("rehearsal", {}))
        merged.update(traffic.get("rehearsal", {}))
    return {k: _hashable(v) for k, v in merged.items()}


def traffic_parameters(traffic: dict, rehearse: bool = False
                       ) -> Dict[str, Any]:
    """What a traffic mix hands its runner; ``rehearse`` lays the mix's tiny
    CPU twin (``rehearsal_parameters``) over it."""
    return {**traffic["parameters"],
            **(traffic.get("rehearsal_parameters", {}) if rehearse else {})}


def build_config(overrides: Dict[str, Any], save_dir: str, seed: int):
    """The program's ``Config`` for one run: the cell's overrides, the seed,
    and the directory the program writes its logs and spans to. Nothing
    else — every ``auto`` switch resolves as ``cli.train`` resolves it."""
    from r2d2_tpu.config import Config
    return Config().replace(**{**overrides, "runtime.save_dir": save_dir,
                               "runtime.seed": int(seed)})


def load_named(kind: str, name: str):
    """Module ``benchmarks/<kind>/<name>.py`` (a runner, a plain reference, a
    per-layer metric's reader; with ``kind`` "" a module of ``benchmarks/``
    itself), found by the name a data file gives it."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"{kind or 'benchmarks'} has no {name!r}: no file "
                         f"{path}")
    return importlib.import_module(
        ".".join(part for part in ("benchmarks", kind, name) if part))


def costs_of(config: dict):
    """The count of a configuration's model work: the module
    ``benchmarks/<name>.py`` that the configuration names under ``costs``, as
    it names its ``reference`` and its ``scopes``. Every such module gives
    ``step_flops(cfg, action_dim)``, the model FLOPs of one train step; one
    may give counts of single layers beside it (``experts_flops``). None for
    a configuration that names no count: its step has no share of a peak."""
    name = config.get("costs")
    return None if name is None else load_named("", name)


def reader_of(metric_name: str):
    """The reader of a per-layer metric. A reading that moves another
    end-to-end metric in some cells is declared again there under
    ``<cells' mix>.<reading>`` (``anakin.train_step_ms``): the part after the
    last dot names the file, so the second entry needs no second reader."""
    return load_named("layer_metrics", metric_name.rsplit(".", 1)[-1])


# ---------------------------------------------------------------------------
# instruments


class CompileWatch:
    """Counts, through ``jax.monitoring``, every program this process builds
    or loads from the persistent cache (``builds``; both stall a dispatch,
    so a measured window must see none), and the cache's hits and misses."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.builds = 0
        self.build_s = 0.0
        self.hits = 0
        self.misses = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.BUILD:
            self.builds += 1
            self.build_s += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def __enter__(self) -> "CompileWatch":
        import jax.monitoring as m
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as m
        m.unregister_event_duration_listener(self._on_duration)
        m.unregister_event_listener(self._on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"builds": self.builds, "build_s": round(self.build_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


class HostSpans:
    """The benchmark's own host spans, around its calls into the program.
    Each is timed on the host clock and, while a trace is being taken, also
    written into the profiler's trace (``jax.profiler.TraceAnnotation``), so
    that a gap on the device can be laid to what the host was doing."""

    def __init__(self):
        self.rows: List[Tuple[str, float, float]] = []   # name, t0, t1

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = 0.0,
                  until: float = float("inf")) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.rows
                if n == name and t0 >= since and t1 <= until]


def mark(name: str, **stats) -> None:
    """An instant in the profiler's trace (no-op when none is being taken)."""
    import jax
    with jax.profiler.TraceAnnotation(name, **stats):
        pass


class DeviceTrace:
    """One profiler capture into ``out_dir``. The Python tracer is off: it
    slows the host and fattens the file, and the reduction does not read it.
    The programs' HLO stays in (``enable_hlo_proto``): it is where the scope
    of each operation is. ``begin``/``end`` bracket the traced window with
    markers; ``bench_clock`` carries the wall clock, so that spans the
    program wrote on ``time.time()`` can be laid onto the trace's clock."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.path: Optional[str] = None
        self.active = False

    def begin(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = True
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.active = True
        mark(MARK_CLOCK, unix_ns=time.time_ns())
        mark(MARK_WINDOW_BEGIN)

    def end(self) -> None:
        import jax
        if not self.active:
            return
        mark(MARK_WINDOW_END)
        self.active = False
        jax.profiler.stop_trace()
        from benchmarks.trace.dump import newest_xplane
        self.path = newest_xplane(self.out_dir)


def stamp(process_start: float, what: str) -> None:
    """One line of the set-up's timeline: seconds since the process began."""
    print(f"t+{time.perf_counter() - process_start:.1f}s {what}", flush=True)


def require_chips(chips: int):
    """The devices a cell measures on, or ``SystemExit``: a number from this
    benchmark is a TPU's number, and a cell needs the chips it names."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, found platform={devs[0].platform!r} "
            f"({devs[0].device_kind}); --rehearse runs the tiny CPU twin, "
            "which prints no metric")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs


def device_facts() -> Dict[str, Any]:
    """The device as JAX reports it, and the peak memory of the fullest chip
    (0 where the backend keeps no such count, as on the CPU)."""
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
