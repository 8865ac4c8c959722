#!/usr/bin/env python3
"""Record the traced run of a cell and keep its profiler trace.

    python3 benchmark/record_trace.py --workload v1024-replay --seed <n> \\
        --seconds 30 --out traces
    python3 benchmark/record_trace.py --workload v1024-replay --seed 1 \\
        --seconds 0.1 --validators 64 --job-events 3000 --sync 512 \\
        --out <dir> --xz

The run is the harness's own (benchmark/run.py `start`, its profiler
options, the cell's workload code), on its configuration and mix, optionally
cut smaller (`--validators`, `--job-events`, `--sync`). The harness
reduces a trace and deletes it; this keeps the `.xplane.pb` in `--out`
(xz-compressed with `--xz`, as the test fixtures are) and reads its op
metadata as well (benchmark/xplane_ops.py).

Prints one JSON object: the run's end-to-end numbers, its per-layer
metrics from BENCHMARK.json, the metrics of benchmark/metrics/ that
read the trace's op metadata (OPS_METRICS), the op-metadata sums, and
the trace's path. Off a TPU, with --need-chip 1, it exits 2.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import lzma
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as harness  # noqa: E402

# Per-layer metrics that read `run.trace_ops`, which only this tool
# sets (benchmark/run.py deletes its trace before the readers run).
OPS_METRICS = (
    "fused_stage_ms_per_pass.frontier",
    "fused_stage_ms_per_pass.fame",
    "fused_stage_ms_per_pass.round_received",
    "fused_stage_ms_per_pass.timestamps",
    "device_idle_ms_per_pass.engine_apply",
    "device_idle_ms_per_pass.engine_stage",
    "fused_redos_per_pass",
)


class KeptTraceRun(harness.Run):
    """A harness run whose traced window keeps its trace in `out_dir`."""

    out_dir = "."
    xz = False

    @contextlib.contextmanager
    def profiled(self):
        if not self.trace:
            yield
            return
        import jax

        from benchmark import trace_reduce, xplane_ops

        tmp = tempfile.mkdtemp(prefix="benchmark_trace_")
        # The profiler options of harness.Run.profiled.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
            try:
                src, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                 recursive=True)
                os.makedirs(self.out_dir, exist_ok=True)
                path = os.path.join(self.out_dir, os.path.basename(src))
                shutil.move(src, path)
                self.trace_summary = trace_reduce.reduce_file(path)
                self.trace_ops = xplane_ops.summarize(path)
                if self.xz:
                    with open(path, "rb") as fh, lzma.open(
                            path + ".xz", "wb", preset=9) as out:
                        shutil.copyfileobj(fh, out)
                    os.remove(path)
                    path += ".xz"
                self.trace_path = path
            finally:
                shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--xz", action="store_true")
    ap.add_argument("--validators", type=int)
    ap.add_argument("--job-events", type=int)
    ap.add_argument("--sync", type=int)
    ap.add_argument("--need-chip", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from benchmark.arrivals import load_mix

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_json(
        os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = load_mix(cell["traffic"])
    if args.validators:
        config["validators"] = args.validators
    if args.job_events:
        mix["job_events"] = args.job_events
    if args.sync:
        mix["sync"] = args.sync
    KeptTraceRun.out_dir, KeptTraceRun.xz = args.out, args.xz
    harness.Run = KeptTraceRun  # start() builds its run from this name
    try:
        run = harness.start(args.workload, args.seed, args.seconds, True,
                            config=config, mix=mix,
                            need_chip=bool(args.need_chip))
    except harness.NoChip as exc:
        print(f"record_trace: {exc}", file=sys.stderr)
        return 2
    line = harness.result_line(run)
    line["e2e"] = run.e2e
    line["counters"] = run.counters
    for name in OPS_METRICS:
        line["metrics"][name] = harness.metric_reader(name)(run)
    ops = run.trace_ops
    line["trace_ops"] = dict(ops.__dict__)
    line["trace"] = run.trace_path
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
