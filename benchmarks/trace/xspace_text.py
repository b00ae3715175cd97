"""Captures as text. ``jax.profiler.ProfileData`` reads an ``XSpace`` from
its text form, so a capture can be built by hand in a test, or cut down from
a recorded one and kept in git as a small file that says what it holds.

A capture here is plain data::

    {plane name: {line name: [(event name, start_ns, duration_ns, stats)]}}

with ``stats`` a dict of str, int or float values. Only what the reduction
reads is kept: names, times and stats; ids and display names are made up.

    python3 -m benchmarks.trace.xspace_text <in.xplane.pb> <out.txt.gz> \\
        --from-ns A --to-ns B --host-names dispatch block_wait ...
"""

import argparse
import gzip
import json
import sys
from typing import Any, Dict, List, Sequence, Tuple

Event = Tuple[str, float, float, Dict[str, Any]]
Capture = Dict[str, Dict[str, List[Event]]]


def _quote(s: str) -> str:
    return json.dumps(s)             # text-format strings take C escapes


def to_text(capture: Capture) -> str:
    out: List[str] = []
    for p, (plane, lines) in enumerate(capture.items(), 1):
        names: Dict[str, int] = {}
        stat_names: Dict[str, int] = {}
        body: List[str] = []
        for l, (line, events) in enumerate(lines.items(), 1):
            body.append(f"  lines {{ id: {l} name: {_quote(line)} "
                        "timestamp_ns: 0")
            for name, start, dur, stats in events:
                meta = names.setdefault(name, len(names) + 1)
                row = (f"    events {{ metadata_id: {meta} offset_ps: "
                       f"{int(round(start * 1000))} duration_ps: "
                       f"{int(round(dur * 1000))}")
                for key, value in stats.items():
                    sid = stat_names.setdefault(key, len(stat_names) + 1)
                    if isinstance(value, str):
                        val = f"str_value: {_quote(value)}"
                    elif isinstance(value, float):
                        val = f"double_value: {value!r}"
                    else:
                        val = f"int64_value: {int(value)}"
                    row += f" stats {{ metadata_id: {sid} {val} }}"
                body.append(row + " }")
            body.append("  }")
        out.append(f"planes {{ id: {p} name: {_quote(plane)}")
        out.extend(body)
        for name, meta in names.items():
            out.append(f"  event_metadata {{ key: {meta} value {{ id: {meta} "
                       f"name: {_quote(name)} }} }}")
        for name, sid in stat_names.items():
            out.append(f"  stat_metadata {{ key: {sid} value {{ id: {sid} "
                       f"name: {_quote(name)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


def write(capture: Capture, path: str) -> None:
    """``.gz`` or plain text; ``load`` reads either."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        f.write(to_text(capture))


def load(path: str):
    """A ``ProfileData`` from a text capture (``.gz`` or plain)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return ProfileData.from_text_proto(f.read())


def cut(raw: bytes, lo: float, hi: float, host_names: Sequence[str],
        device_lines: Sequence[str] = ("XLA Modules", "XLA Ops", "Steps")
        ) -> Capture:
    """A recorded capture (the bytes of an ``.xplane.pb``) cut down to what
    the reduction reads: on each TPU plane the events of ``device_lines``
    inside [lo, hi] ns, each operation named by its instruction alone and
    carrying its scope path (``op_name``, from the program's HLO, which the
    text form cannot hold) and whether it is a Pallas kernel; on the host
    plane the events named in ``host_names``."""
    from jax.profiler import ProfileData

    from benchmarks.trace import hlo_names, reduce
    scopes = hlo_names.program_scopes(raw)
    out: Capture = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        kept: Dict[str, List[Event]] = {}
        if reduce.DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines
                     if line.name in device_lines}
            modules = sorted(lines.get(reduce.MODULE_LINE, []),
                             key=lambda e: e.start_ns)
            for name, events in lines.items():
                rows: List[Event] = []
                for e in events:
                    if e.start_ns < lo or e.start_ns + e.duration_ns > hi:
                        continue
                    stats: Dict[str, Any] = {}
                    label = e.name
                    if name == reduce.OP_LINE:
                        label = "%" + hlo_names.instruction_name(e.name)
                        owner = next((m.name for m in modules
                                      if m.start_ns <= e.start_ns
                                      < m.start_ns + m.duration_ns), "")
                        path = scopes.get(owner, {}).get(label[1:], "")
                        if path:
                            stats["op_name"] = path
                        if reduce.KERNEL_TARGET in e.name:
                            stats["kernel"] = 1
                    rows.append((label, e.start_ns, e.duration_ns, stats))
                kept[name] = rows
        elif plane.name == reduce.HOST_PLANE:
            for line in plane.lines:
                rows = [(e.name, e.start_ns, e.duration_ns,
                         {k: v for k, v in e.stats})
                        for e in line.events if e.name in host_names
                        and lo <= e.start_ns <= hi]
                if rows:
                    kept[line.name] = rows
        if kept:
            out[plane.name] = kept
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("src", help="an .xplane.pb")
    p.add_argument("dst", help="the text capture to write (.txt or .txt.gz)")
    p.add_argument("--from-ns", type=float, default=0.0)
    p.add_argument("--to-ns", type=float, default=float("inf"))
    p.add_argument("--host-names", nargs="*", default=[])
    args = p.parse_args(argv)
    with open(args.src, "rb") as f:
        capture = cut(f.read(), args.from_ns, args.to_ns, args.host_names)
    write(capture, args.dst)
    print({pl: {ln: len(ev) for ln, ev in lines.items()}
           for pl, lines in capture.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
