"""Driver for a configuration that is one validator's consensus engine
with its participant axis sharded over the cell's chips:
`IncrementalEngine(mesh=...)` on the mesh that `node.core.engine_mesh`
builds, as `Core(engine_mesh=<chips>)` builds it, sized by
`engine_sizes` as `Core` sizes it, so that it grows as a node's grows.

Closed loop (`replay`), as benchmark/drivers/engine.py's: jobs of
`job_events` events from a fresh engine, back to back, in `sync`-event
syncs; a warm-up job in set-up; whole jobs until the first job end at
or past `--seconds`. The DAG is drawn from the mix's `dag_seed`;
`--seed` renames its validators.

Besides engine.py's counters, it sums the engine's per-pass counts
(`IncrementalEngine.pass_counts`): chunks of the round-received sweep
and of the timestamp loop, the lanes swept, and the bytes the sweep
must read (benchmark/sweep_bytes.py).

The check, once the engines are gone: every event the window's passes
processed against benchmark/reference_sharded.py over the job's DAG,
on the same chips.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from benchmark import reference_sharded
from benchmark.drivers.engine import BLOCKING, Job, compare
from benchmark.gossip import random_gossip, relabelled
from benchmark.sweep_bytes import sweep_bytes

# Per-pass counts summed into run.counters, under these names.
PASS_COUNTS = {"rr_chunks": "rr_chunks", "ts_chunks": "ts_chunks",
               "au": "lanes_swept", "n_und": "undecided_lanes"}


def run(run) -> None:
    if run.mix["loop"] != "closed":
        raise ValueError("the mesh engine driver runs closed loops only")
    replay(run)


def replay(run) -> None:
    import jax

    from babble_tpu.node.core import engine_mesh, engine_sizes
    from babble_tpu.ops.incremental import IncrementalEngine

    cfg, mix = run.config, run.mix
    n = int(cfg["validators"])
    chips = len(run.devices)
    mesh = engine_mesh(run.devices, n, chips)
    devices = 1 if mesh is None else mesh.devices.size
    dag = relabelled(random_gossip(n, int(mix["job_events"]),
                                   int(mix["dag_seed"])), run.seed)
    ts = np.arange(dag.e, dtype=np.int64)
    sizes = engine_sizes(n, cfg.get("engine_opts"))
    sync = int(mix["sync"])
    engine = functools.partial(IncrementalEngine, mesh=mesh)

    def new_job():
        with jax.profiler.TraceAnnotation("bench_new_engine"):
            return Job(engine, n, sizes, dag, ts, sync)

    # Set-up: one whole job compiles every shape a job uses.
    warm = new_job()
    while not warm.done:
        warm.step()
    warm.close()
    del warm
    gc.collect()
    run.setup_done()

    ordered = passes = 0
    blocked_ns = swept_bytes = 0
    sums = dict.fromkeys(PASS_COUNTS.values(), 0)
    jobs = []
    compiles0 = run.compiles
    t0 = time.monotonic()
    with run.profiled():
        while not jobs or time.monotonic() - t0 < run.seconds:
            job = new_job()
            while not job.done:
                delta = job.step()
                if delta is not None:
                    ordered += len(delta.new_received)
                    passes += 1
                    eng = job.eng
                    blocked_ns += sum(eng.phase_ns.get(p, 0)
                                      for p in BLOCKING)
                    pc = eng.pass_counts
                    for key, name in PASS_COUNTS.items():
                        sums[name] += pc[key]
                    swept_bytes += sweep_bytes(pc["au"], n, pc["iw"],
                                               devices)
            job.close()
            jobs.append(job.results())
            del job
        t1 = time.monotonic()
        run.window = (t0, t1)
    run.counters.update(
        passes=passes, ordered=ordered, jobs=len(jobs),
        compiles_in_window=run.compiles - compiles0,
        engine_blocked_ns=blocked_ns, devices=devices,
        sweep_bytes=swept_bytes, **sums)
    run.e2e["ordered_events_per_s"] = ordered / (t1 - t0)
    run.e2e["setup_s"] = run.setup_s
    gc.collect()
    run.read_memory()

    # The check, once the engines are gone: the reference over the job.
    ref = reference_sharded.consensus(
        n, dag.sp, dag.op, dag.creator, dag.index, dag.coin, ts,
        devices=run.devices[:devices])
    compare(run, jobs, ref)
    run.attempted = ordered
