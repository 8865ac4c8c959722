"""The engine's own tracing: the fused kernel's stage scopes in its op
metadata, the pass spans on the span ring and in a profiler session,
and the phase times a reader sees between passes."""

from __future__ import annotations

import glob
import re
import threading
import time

import numpy as np

from babble_tpu.ops import incremental as inc
from babble_tpu.ops.dag import synthetic_dag
from babble_tpu.ops.incremental import IncrementalEngine
from babble_tpu.telemetry import SpanRing

STAGES = ("frontier", "rounds", "fame", "round_received", "timestamps",
          "pack")
STAGE_SPANS = {"engine.stage", "engine.ingest", "engine.closure",
               "engine.tables", "engine.windows", "engine.fused_enqueue"}
COLLECT_SPANS = {"engine.collect", "engine.stage_wait", "engine.pull_wait",
                 "engine.pull_xfer", "engine.apply", "engine.apply.rounds",
                 "engine.apply.fame", "engine.apply.received"}


def test_fused_kernel_ops_carry_their_stage_scope():
    import jax
    import jax.numpy as jnp

    n, cap, k, rcap, lanes, w = 4, 64, 8, 16, 64, 16

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    c1, sc = cap + 1, s(())
    args = (s((n, k, n)), s((n, k)), s((n,)), s((cap, n)), s((n, n, k)),
            s((cap,)), s((n, k)), s((rcap, n)), s((rcap, n)), s((n,)),
            s((n,)), sc, sc, s((c1,)), s((c1,)), s((c1,)),
            s((c1,), jnp.int8), sc, sc, s((cap,)), s((cap,)),
            s((rcap, n)), s((rcap,), jnp.bool_), s((n, k)), s((n, k)), sc,
            sc, s((lanes,)), sc, sc)
    text = inc._consensus_fused.lower(
        *args, n=n, sm=3, rcap=rcap, bp=16, rw=w, iw=w, cb=16, tc=4,
        tw=16, rl=lanes).compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(_consensus_fused\)/(\w+)', text))
    assert set(STAGES) <= scopes


def feed(eng, dag, sync, stamps):
    """Pipelined syncs as a node's worker feeds them; `stamps` maps each
    pass_id to [dispatch entry ns, collect return ns, phase_ns]."""
    pending, k = None, 0
    while True:
        if k < dag.e:
            hi = min(k + sync, dag.e)
            eng.append_batch(dag.self_parent[k:hi], dag.other_parent[k:hi],
                             dag.creator[k:hi], dag.index[k:hi],
                             dag.coin[k:hi], np.arange(k, hi))
            k = hi
        if pending is not None:
            eng.collect(pending)
            stamps[pending.pass_id][1:] = [time.perf_counter_ns(),
                                           eng.phase_ns]
        t = time.perf_counter_ns()
        pending = eng.dispatch()
        if pending is None:
            return
        stamps[pending.pass_id] = [t, None, None]


def test_engine_spans_link_each_pass_across_threads(monkeypatch):
    # Timestamp chunks of 16 lanes: a pass's ts_chunks counts them.
    monkeypatch.setattr(inc, "TS_CHUNK", 16)
    ring = SpanRing(4096)
    eng = IncrementalEngine(4, capacity=2048, block=64, k_capacity=1024,
                            trace=ring)
    dag, _ = synthetic_dag(4, 1500, seed=3)
    stamps: dict = {}
    # A first sync of about 35 rounds, past the first pass's predicted
    # 32-round windows: one redo.
    feed(eng, dag, 500, stamps)
    eng.close()
    spans = [sp for sp in ring.snapshot() if sp["cat"] == "engine"]
    by_pass: dict = {}
    for sp in spans:
        by_pass.setdefault(sp["args"]["pass_id"], []).append(sp)
    assert sorted(by_pass) == sorted(stamps) and len(stamps) >= 3
    redos = 0
    for pid, group in by_pass.items():
        names = [sp["name"] for sp in group]
        assert set(names) - {"engine.redo"} == STAGE_SPANS | COLLECT_SPANS
        # A redo re-enqueues the kernel and pulls its delta again.
        again = 1 + names.count("engine.redo")
        for x in STAGE_SPANS | COLLECT_SPANS:
            twice = x in ("engine.fused_enqueue", "engine.pull_wait",
                          "engine.pull_xfer")
            assert names.count(x) == (again if twice else 1), x
        stage, = [sp for sp in group if sp["name"] == "engine.stage"]
        coll, = [sp for sp in group if sp["name"] == "engine.collect"]
        t_dispatch, t_collected, _ = stamps[pid]
        assert t_dispatch <= stage["t0"] <= stage["t1"] <= t_collected
        assert stage["t1"] <= coll["t1"] <= t_collected
        for sp in group:
            if sp["name"] in STAGE_SPANS - {"engine.fused_enqueue"}:
                assert stage["t0"] <= sp["t0"] <= sp["t1"] <= stage["t1"]
            elif sp["name"] in COLLECT_SPANS | {"engine.redo"}:
                assert coll["t0"] <= sp["t0"] <= sp["t1"] <= coll["t1"]
        assert stage["args"]["events"] > 0
        assert all(sp["args"]["bytes"] > 0 for sp in group
                   if sp["name"] == "engine.pull_xfer")
        apply, = [sp for sp in group if sp["name"] == "engine.apply"]
        assert apply["args"]["newly"] >= 0
        assert apply["args"]["ts_chunks"] == -(-apply["args"]["newly"] // 16)
        for sp in group:
            if sp["name"] == "engine.redo":
                redos += 1
                assert sp["args"]["reason"] in ("rcap", "window", "cb")
    assert redos == eng.redo_count >= 1
    assert any(sp["args"]["ts_chunks"] > 1 for sp in spans
               if sp["name"] == "engine.apply")
    # The phase times a collect publishes are its pass's span stamps.
    last = {sp["name"]: sp for sp in by_pass[max(by_pass)]}
    ph = stamps[max(by_pass)][2]
    assert ph["coords"] == (last["engine.closure"]["t1"]
                            - last["engine.ingest"]["t0"])
    assert ph["fd_fold"] == (last["engine.tables"]["t1"]
                             - last["engine.tables"]["t0"])
    assert ph["apply"] == (last["engine.apply"]["t1"]
                           - last["engine.apply"]["t0"])
    assert ph["c_pull"] == ph["c_pull_wait"] + ph["c_pull_xfer"]


def test_phase_times_stay_with_the_collected_pass():
    """A reader after the next dispatch sees the collected pass whole,
    even while the staging worker is inside the next pass."""
    eng = IncrementalEngine(4, capacity=512, block=64, k_capacity=64)
    dag, _ = synthetic_dag(4, 120, seed=5)

    def append(lo, hi):
        eng.append_batch(dag.self_parent[lo:hi], dag.other_parent[lo:hi],
                         dag.creator[lo:hi], dag.index[lo:hi],
                         dag.coin[lo:hi], np.arange(lo, hi))

    append(0, 60)
    eng.collect(eng.dispatch())
    collected = eng.phase_ns
    assert "c_pull" in collected and "coords" in collected
    gate, inside = threading.Event(), threading.Event()
    sync_device = eng._sync_device

    def held(*a):
        inside.set()
        gate.wait(30)
        return sync_device(*a)

    eng._sync_device = held  # the first step of staging
    append(60, 120)
    pp = eng.dispatch()
    try:
        assert inside.wait(30)
        assert eng.phase_ns is collected
        assert eng.phase_ns["c_pull"] == collected["c_pull"]
    finally:
        gate.set()
    eng.collect(pp)
    eng.close()
    assert eng.phase_ns is not collected and "c_pull" in eng.phase_ns


def test_span_reaches_a_profiler_session_from_a_disabled_ring(tmp_path):
    import jax

    ring = SpanRing(0)
    with jax.profiler.trace(str(tmp_path)):
        with ring.span("x", cat="test", k=3) as rec:
            pass
    assert rec.t1 >= rec.t0 and len(ring) == 0
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    hits = [ev for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for ev in line.events if ev.name == "x"]
    assert len(hits) == 1
    assert ("k", 3) in list(hits[0].stats)


def test_a_core_records_its_engine_passes_on_its_ring():
    from babble_tpu import crypto
    from babble_tpu.hashgraph.inmem_store import InmemStore
    from babble_tpu.node import Core

    key = crypto.key_from_seed(9100)
    participants = {"0x" + crypto.pub_key_bytes(key).hex().upper(): 0}
    ring = SpanRing(256)
    core = Core(0, key, participants, InmemStore(participants, 1000),
                engine="tpu", trace=ring,
                engine_opts={"capacity": 64, "block": 64, "k_capacity": 8})
    core.init()
    core.run_consensus()
    core.hg.engine.close()
    assert core.hg.engine.trace is ring
    names = {sp["name"] for sp in ring.snapshot()}
    assert {"consensus_pass", "engine.stage", "engine.collect"} <= names
    assert "engine_c_pull" in core.phase_ns
