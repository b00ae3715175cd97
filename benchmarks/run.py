"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``benchmarks/configs/``) under a traffic mix (``benchmarks/workloads/``,
which also names the runner under ``benchmarks/runners/``). With ``--trace 0``
the last line of standard output carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, each read by its own file under
``benchmarks/layer_metrics/``. This file knows no cell, configuration or
metric by name.

Without a TPU, or with fewer chips than the cell names, it exits non-zero and
prints no result. ``--rehearse 1`` runs each file's tiny CPU twin instead, to
find wrong paths and arguments before a chip call; its last line has the same
keys and no metric.
"""

import time

PROCESS_START = time.perf_counter()

import argparse
import dataclasses
import json
import os
import shutil
import sys
import threading
from typing import Any, Dict, Optional

# the script's own directory leads sys.path and would shadow the standard
# library's ``trace`` with benchmarks/trace; the checkout's root goes there
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.dirname(os.path.abspath(__file__)) in sys.path[:1]:
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclasses.dataclass
class RunContext:
    """What a runner is given."""
    cfg: Any                       # the program's Config for this run
    reference: str                 # the configuration's plain reference
    traffic: Dict[str, Any]        # the traffic mix's parameters
    seed: int
    seconds: float
    rehearse: bool
    process_start: float
    compiles: Any                  # harness.CompileWatch
    spans: Any                     # harness.HostSpans
    trace: Optional[Any]           # harness.DeviceTrace, or None


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader is given."""
    cfg: Any
    values: Dict[str, Any]         # what the runner measured on the host
    facts: Dict[str, Any]          # counts the runner resolved
    trace: Optional[Any]           # trace.reduce.TraceSummary, or None
    device_kind: str
    config: Optional[Dict[str, Any]] = None   # the configuration's file


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    try:
        import r2d2_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    from benchmarks import harness
    from r2d2_tpu.utils.platform import enable_compile_cache, pin_platform

    harness.stamp(PROCESS_START, "arguments read")
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    config = harness.config_doc(bench, cell["config"])
    traffic = harness.traffic_doc(cell["traffic"])
    runner = harness.load_named("runners", traffic["runner"])

    harness.stamp(PROCESS_START, "runner imported")
    # libtpu logs under /tmp/tpu_logs unless told where: keep it in the checkout
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(harness.OUT_DIR, "tpu_logs"))
    pin_platform()
    cache_dir = enable_compile_cache()
    # Importing the program takes 22 s on the chip's machine from a helper
    # thread (36-39 s from this one, measured on three machines; PERF.md,
    # set-up) and reaching the chip 6-9 s; every run pays both, so they
    # overlap. A failed import shows again, on this thread, in runner.run.
    loader = threading.Thread(target=runner.load_program, daemon=True)
    loader.start()
    if not args.rehearse:
        harness.require_chips(cell["chips"])
    harness.stamp(PROCESS_START, "devices found")
    loader.join()
    harness.stamp(PROCESS_START, "program imported")

    out_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    cfg = harness.build_config(
        harness.program_overrides(config, traffic, bool(args.rehearse)),
        out_dir, args.seed)
    print(f"cell {cell['name']}: config {cell['config']} traffic "
          f"{cell['traffic']} runner {traffic['runner']} chips "
          f"{cell['chips']} seed {args.seed} seconds {args.seconds} trace "
          f"{args.trace} compile_cache {cache_dir}", flush=True)

    spans = harness.HostSpans()
    trace = (harness.DeviceTrace(os.path.join(out_dir, "trace"))
             if args.trace else None)
    with harness.CompileWatch() as compiles:
        result = runner.run(RunContext(
            cfg=cfg, reference=config["reference"],
            traffic=harness.traffic_parameters(traffic, bool(args.rehearse)),
            seed=args.seed, seconds=args.seconds, rehearse=bool(args.rehearse),
            process_start=PROCESS_START, compiles=compiles, spans=spans,
            trace=trace))
        print(f"programs: {json.dumps(compiles.snapshot())}", flush=True)
    print(f"checks: {json.dumps(result['checks'])}", flush=True)
    print(f"reference: {json.dumps(result['reference'])}", flush=True)
    print(f"facts: {json.dumps(result['facts'])}", flush=True)

    device = harness.device_facts()
    line: Dict[str, Any] = {
        "correct": all(result["checks"].values()),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]), "metrics": {}, "device": device,
    }
    level = "per_layer" if args.trace else "end_to_end"
    declared = harness.cell_metrics(bench, cell["name"], level)
    if args.trace:
        from benchmarks.trace import reduce
        summary = reduce.summarize(
            trace.path, scopes=harness.scope_table(config),
            host_names={name for name, _, _ in spans.rows},
            external_spans=result.get("external_spans"), chips=cell["chips"])
        if summary is not None:
            device.update(summary.device_window())
            line["breakdown"] = summary.breakdown()
            print(f"self time / busy time: "
                  f"{summary.self_total_s() / summary.busy_s():.4f}; by scope "
                  f"{json.dumps(summary.self_by_scope())}", flush=True)
        ctx = MetricContext(cfg=cfg, values=result["values"],
                            facts=result["facts"], trace=summary,
                            device_kind=device["kind"], config=config)
        for m in declared:
            value = harness.reader_of(m["name"]).read(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
    if args.rehearse:
        # a CPU run has no metric: the readers were only exercised
        print(f"readers that found something: {sorted(line['metrics'])}")
        line.update(metrics={}, rehearsal=True)
        line.pop("breakdown", None)
    elif not args.trace:
        for m in declared:
            line["metrics"][m["name"]] = {
                "value": float(result["values"][m["name"]]),
                "unit": m["unit"]}
    # each number the reference check compared, beside its limit: the last
    # key of the line and the last lines on standard error
    reference = result["reference"]
    line["compared"] = {name: [error, reference["limits"][name]]
                        for name, error in reference["errors"].items()}
    for name, (error, limit) in line["compared"].items():
        print(f"compared {name}: {error:.6g} (limit {limit:g})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
