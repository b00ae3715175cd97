"""Noise-aware bench regression gate (ISSUE 7): compare fresh
``E2E_*`` artifacts against the ``bench`` section of
``BASELINE.json``.

Every perf round leaves a JSON artifact (tools/e2e_bench.py A/Bs), but
nothing ever COMPARED two rounds — a 20% throughput regression would
merge silently as long as tests stayed green. This gate closes that hole:

  * ``--update`` snapshots the throughput metrics of every artifact in
    ``--dir`` into ``BASELINE.json["bench"]`` (one dotted-path → value
    map per artifact file);
  * the default run re-extracts the same metrics from the CURRENT
    artifacts and fails (exit 1) when any falls more than its tolerance
    below baseline.

Noise policy — the reason tolerances are per-metric, not one number:
single e2e cells swing ±10% run-to-run on a small shared host (2-core
scheduling noise; measured in rounds 8–11), which is why the A/B
harnesses run INTERLEAVED repeats and quote per-arm medians. The gate
mirrors that: ``*_ratio`` headlines (already medians of interleaved
arms) get the tight tolerance, raw ``*_per_sec`` cells (single runs) the
loose one — tight enough that the acceptance fixture (a synthetic 20%
throughput drop) always fails, loose enough that honest re-runs of the
same tree pass. Watched metrics are HIGHER-IS-BETTER by construction
(throughputs, speedups, on/off ratios); improvements never fail, they
just become the new floor at the next ``--update``.

Cost gate (ISSUE 9): alongside the wall-clock bench metrics, the
``costs`` section of BASELINE.json snapshots the XLA per-program cost
table (telemetry/costmodel.gate_table — flops / bytes accessed / buffer
sizes of every step factory at a pinned tiny config, CPU-pinned so the
numbers are backend-independent). Unlike the noise-tolerant bench gate,
the costs comparison is EXACT-match (analytic counts are deterministic):
a refactor that silently doubles a step's FLOPs or bytes fails ``make
regress`` even on wall-clock-noisy hosts, in BOTH directions. ``--update``
re-baselines it like the bench metrics; ``--skip-costs`` skips the
recompute (it costs ~20-30 s of tiny-config compiles).

    python -m r2d2_tpu.tools.regress                      # gate (make regress)
    python -m r2d2_tpu.tools.regress --update             # re-baseline
    python -m r2d2_tpu.tools.regress --artifacts E2E_r11.json
    python -m r2d2_tpu.tools.regress --skip-costs         # bench only
"""

import glob
import json
import os
import sys
from typing import Dict, List, Optional

# (suffix/substring match on the metric's KEY, tolerance as allowed
# relative drop). First match wins, top to bottom.
DEFAULT_TOLERANCES = (
    ("_ratio", 0.10),          # interleaved-repeat medians (A/B headlines)
    ("speedup", 0.15),         # derived from two single-run cells
    ("vs_baseline", 0.15),
    ("_per_sec", 0.15),        # raw single-run cells (±10% host noise)
)
_WATCH = tuple(k for k, _ in DEFAULT_TOLERANCES)
DEFAULT_GLOBS = ("E2E_*.json",)


def metric_tolerance(path: str, override: Optional[float] = None) -> float:
    if override is not None:
        return override
    key = path.rsplit(".", 1)[-1]
    for pat, tol in DEFAULT_TOLERANCES:
        if key == pat or key.endswith(pat) or pat in key:
            return tol
    return 0.15


def extract_metrics(obj, prefix: str = "") -> Dict[str, float]:
    """Flatten an artifact to {dotted.path: value} over the watched
    throughput keys. Lists are skipped (the ``*_cells`` arrays are the
    noise the medians exist to absorb), as is anything under a
    ``config`` block."""
    out: Dict[str, float] = {}
    if not isinstance(obj, dict):
        return out
    for k, v in obj.items():
        path = f"{prefix}.{k}" if prefix else k
        if k == "config":
            continue
        if isinstance(v, dict):
            out.update(extract_metrics(v, path))
        elif isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        elif any(k == p or k.endswith(p) or p in k for p in _WATCH):
            out[path] = float(v)
    return out


def load_artifact(path: str) -> Optional[dict]:
    """The artifact's JSON object; artifacts are single-object files
    (possibly one JSON line). None when unreadable/unparseable."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def collect(run_dir: str, patterns=DEFAULT_GLOBS,
            names: Optional[List[str]] = None) -> Dict[str, dict]:
    """{artifact filename: metrics} for every readable artifact in
    ``run_dir`` matching the globs (or the explicit ``names``)."""
    if names:
        files = [os.path.join(run_dir, n) for n in names]
    else:
        files = sorted(p for pat in patterns
                       for p in glob.glob(os.path.join(run_dir, pat)))
    out = {}
    for path in files:
        doc = load_artifact(path)
        if doc is None:
            continue
        metrics = extract_metrics(doc)
        if metrics:
            out[os.path.basename(path)] = metrics
    return out


def compare(baseline: Dict[str, dict], current: Dict[str, dict],
            tolerance: Optional[float] = None) -> List[dict]:
    """One row per baselined metric: ok / REGRESSION / missing. New
    artifacts/metrics absent from the baseline are NOT rows — they join
    at the next ``--update``."""
    rows = []
    for fname, metrics in sorted(baseline.items()):
        cur = current.get(fname)
        for path, base in sorted(metrics.items()):
            tol = metric_tolerance(path, tolerance)
            row = {"artifact": fname, "metric": path, "baseline": base,
                   "tolerance": tol}
            if cur is None or path not in cur:
                # a vanished artifact/metric is a gate failure too: the
                # silent way to pass is to stop producing the number
                row.update({"current": None, "status": "missing"})
            else:
                value = cur[path]
                row["current"] = value
                if base > 0 and value < (1.0 - tol) * base:
                    row["status"] = "REGRESSION"
                    row["drop_pct"] = round(100.0 * (1.0 - value / base), 1)
                else:
                    row["status"] = "ok"
            rows.append(row)
    return rows


def _current_costs():
    # CPU-pinned with a >= 2-device virtual mesh (the sharded variant)
    # so the snapshot is identical on a TPU host and the test
    # container; a no-op when a wide-enough backend is already
    # initialized (the pin only binds before first backend init).
    # gate_table() itself memoizes per process; the attribute lookup
    # stays late-bound so tests can stub the recompute.
    from r2d2_tpu.telemetry import costmodel
    from r2d2_tpu.utils.platform import pin_cpu_platform
    pin_cpu_platform(2)
    return costmodel.gate_table()


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--baseline", default="BASELINE.json")
    p.add_argument("--dir", default=".",
                   help="directory holding the fresh artifacts")
    p.add_argument("--artifacts", nargs="*", default=None,
                   help="explicit artifact filenames (default: the "
                        "E2E_* glob)")
    p.add_argument("--tolerance", type=float, default=None,
                   help="override the per-metric tolerance table with one "
                        "relative-drop bound for everything")
    p.add_argument("--update", action="store_true",
                   help="snapshot the current artifacts' metrics (and the "
                        "cost table) into the baseline and exit")
    p.add_argument("--skip-costs", action="store_true",
                   help="skip the XLA cost-table gate/update (saves the "
                        "~20-30 s of tiny-config compiles)")
    p.add_argument("--costs-rtol", type=float, default=1e-6,
                   help="relative tolerance of the exact-match costs gate")
    p.add_argument("--quiet", action="store_true",
                   help="only print regressions and the verdict")
    args = p.parse_args(argv)

    try:
        with open(args.baseline) as f:
            baseline_doc = json.load(f)
    except FileNotFoundError:
        print(f"no baseline at {args.baseline}", file=sys.stderr)
        return 2

    current = collect(args.dir, names=args.artifacts)

    current_costs = _current_costs

    if args.update:
        baseline_doc["bench"] = current
        n = sum(len(m) for m in current.values())
        msg = (f"baselined {n} metrics from {len(current)} artifact(s) "
               f"into {args.baseline}")
        if not args.skip_costs:
            baseline_doc["costs"] = current_costs()
            msg += (f" + {len(baseline_doc['costs']['programs'])} "
                    "cost-table program(s)")
        with open(args.baseline, "w") as f:
            json.dump(baseline_doc, f, indent=2)
            f.write("\n")
        print(msg)
        return 0

    bench = baseline_doc.get("bench")
    costs_gated = bool(baseline_doc.get("costs")) and not args.skip_costs
    if not bench and not costs_gated:
        # an EMPTY bench section is fine once the costs gate exists —
        # fail only when there is nothing at all to gate against
        print(f"{args.baseline} has no 'bench' section — run with "
              "--update first to snapshot the current artifacts",
              file=sys.stderr)
        return 2

    rows = compare(bench or {}, current, tolerance=args.tolerance)
    bad = [r for r in rows if r["status"] != "ok"]
    for r in rows:
        if args.quiet and r["status"] == "ok":
            continue
        cur = "-" if r["current"] is None else f"{r['current']:.10g}"
        extra = (f"  (-{r['drop_pct']}% > {r['tolerance']:.0%} tolerance)"
                 if r["status"] == "REGRESSION" else "")
        print(f"{r['status']:>10}  {r['artifact']}:{r['metric']} "
              f"base={r['baseline']:.10g} cur={cur}{extra}")

    cost_rows, cost_bad = [], []
    if costs_gated:
        from r2d2_tpu.telemetry.costmodel import compare_cost_tables
        cost_rows = compare_cost_tables(baseline_doc["costs"],
                                        current_costs(),
                                        rtol=args.costs_rtol)
        cost_bad = [r for r in cost_rows if r["status"] != "ok"]
        for r in cost_rows:
            if args.quiet and r["status"] == "ok":
                continue
            cur = "-" if r["current"] is None else f"{r['current']:.10g}"
            extra = (f"  ({r['delta_pct']:+}% vs an exact-match gate)"
                     if r["status"] == "CHANGED" else "")
            print(f"{r['status']:>10}  costs:{r['program']}.{r['metric']} "
                  f"base={r['baseline']:.10g} cur={cur}{extra}")

    print(f"-- {len(rows)} bench metric(s) checked, {len(bad)} failing; "
          f"{len(cost_rows)} cost metric(s) checked, "
          f"{len(cost_bad)} changed")
    return 1 if (bad or cost_bad) else 0


if __name__ == "__main__":
    sys.exit(main())
