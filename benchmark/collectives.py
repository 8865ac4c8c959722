"""Device time of the collectives in a profiler trace's measured window.

    python3 benchmark/collectives.py <file.xplane.pb[.xz]> [record_trace line]

Reads every device plane's "XLA Ops" events through
benchmark/xplane_ops.read_planes and sums, per kind, the device time of
the all-gather, all-reduce, reduce-scatter, collective-permute and
all-to-all ops (their `-start`/`-done` halves included) inside the
`bench_window` span, averaged over the device planes as trace_reduce
averages. `total` is the union of those intervals, so overlapping ops
count once. A trace with no device plane reads empty.

Given the JSON line that benchmark/record_trace.py printed for the same
trace, it also prints the values of the readers that need both
(`collective_ms_per_pass`, `roofline_share.round_received`).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark.trace_reduce import WINDOW_SPAN, clip, union
from benchmark.xplane_ops import read_planes

# Checked in order: all-reduce-scatter is a reduce-scatter.
KINDS = ("all-reduce-scatter", "reduce-scatter", "all-gather", "all-reduce",
         "collective-permute", "all-to-all")


def kind_of(op: str) -> str:
    """`all-gather-start.3` -> `all-gather`; "" for other ops."""
    base = op.split(".", 1)[0]
    for kind in KINDS:
        if base.startswith(kind):
            return "reduce-scatter" if kind == "all-reduce-scatter" else kind
    return ""


def summarize(path: str) -> Dict[str, float]:
    """Seconds per collective kind and `total`, per device plane."""
    planes = read_planes(path)
    win = [(s, e) for p in planes if not p.name.startswith("/device:")
           for events in p.lines.values() for s, e, mid in events
           if p.names.get(mid, "") == WINDOW_SPAN]
    devs = [p for p in planes if p.name.startswith("/device:")
            and "XLA Ops" in p.lines]
    if not win or not devs:
        return {}
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    out: Dict[str, float] = {}
    for p in devs:
        spans = []
        for s, e, mid in p.lines["XLA Ops"]:
            kind = kind_of(p.names.get(mid, ""))
            if kind:
                for cs, ce in clip([(s, e)], lo, hi):
                    out[kind] = out.get(kind, 0.0) + (ce - cs)
                    spans.append((cs, ce))
        out["total"] = out.get("total", 0.0) + sum(
            e - s for s, e in union(spans))
    return {k: v / len(devs) / 1e9 for k, v in out.items()}


def of_run(run) -> Dict[str, float]:
    """The run's collective seconds, from its kept trace (read once)."""
    if getattr(run, "trace_collectives", None) is None:
        path = getattr(run, "trace_path", None)
        run.trace_collectives = summarize(path) if path else {}
    return run.trace_collectives


def main(argv) -> int:
    from types import SimpleNamespace

    from benchmark import run as harness
    from benchmark.xplane_ops import TraceOps

    path = argv[0]
    print(json.dumps({"collective_s": summarize(path)}))
    if len(argv) > 1:
        with open(argv[1]) as fh:
            line = json.loads(fh.read().strip().splitlines()[-1])
        run = SimpleNamespace(
            trace_path=path, counters=line["counters"],
            trace_ops=TraceOps(**line["trace_ops"]),
            devices=[SimpleNamespace(device_kind=line["device"]["kind"])])
        print(json.dumps({name: harness.metric_reader(name)(run) for name in (
            "collective_ms_per_pass", "roofline_share.round_received")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
