"""Driver for a configuration that is one validator catching up on signed
history: a `Core` built as `Node` builds it (node.build_core, from the
configuration's `core` settings), fed every event of the history
through `Core.sync` (wire decode, signature verify on the host pool,
`insert_wire_batch`, the wrap self-event), then, as Node._sync does at
consensus_interval 0 (reference node/node.go:467-487), one consensus
pass inline after each sync: dispatch and collect back to back.

The history comes from benchmark/signed_history.py: the mix's
`job_events` events of `validators - 1` peers, signed, in `sync`-event
columnar batches built once at set-up. Closed loop: each job is a fresh
Core (store and engine) that calls init() and ingests every batch, one
pass per sync, so a job's passes are the same in every job. Jobs run
back to back until the first job end at or past `--seconds`.

The check, per job: the peer events its store holds against the
history (the same hashes and parents) and its own events against init
plus one wrap per sync; those events, exported from the Event objects
in topological order, against benchmark/reference.py
(drivers/engine.compare's four counts); and its committed blocks'
transactions against benchmark/reference_blocks.py."""

from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as np

from benchmark import reference, reference_blocks
from benchmark.drivers.engine import BLOCKING, compare
from benchmark.drivers.testnet import middle_bit
from benchmark.signed_history import generate

# Per-pass counts of the engine (IncrementalEngine.pass_counts) summed
# into run.counters, as the mesh engine driver sums them.
PASS_COUNTS = ("rr_chunks", "ts_chunks")


def _scope(what: str):
    import jax

    return jax.profiler.TraceAnnotation("bench_" + what)


class Job:
    """One catch-up from a fresh Core."""

    def __init__(self, hist, conf):
        from babble_tpu.hashgraph import InmemStore
        from babble_tpu.node.node import build_core
        from babble_tpu.telemetry import ClusterClock, Registry, SpanRing

        self.hist = hist
        self.blocks = []
        self.core = build_core(
            conf, hist.local_id, hist.local_key,
            InmemStore(hist.participants, conf.cache_size),
            self.blocks.append, trace=SpanRing(conf.trace_ring),
            registry=Registry(), clock=ClusterClock())
        self.passes = 0
        self.counts = dict.fromkeys(PASS_COUNTS, 0)
        self.gossip = {"new": 0, "stale": 0, "syncs": 0}

    def run(self):
        """init(), then each sync and the consensus pass after it. No
        other thread takes the core here, so the core lock Node holds
        around both is left out."""
        core = self.core
        engine = core.hg.engine
        core.init()
        for batch in self.hist.batches:
            with _scope("sync"):
                st = core.sync(batch)
            self.gossip["new"] += st["new"]
            self.gossip["stale"] += st["stale"]
            self.gossip["syncs"] += 1
            with _scope("consensus"):
                core.run_consensus()
            self.passes += 1
            for key in PASS_COUNTS:
                self.counts[key] += engine.pass_counts.get(key, 0)

    def phase_total(self, phase: str) -> int:
        return self.core.phase_ns.get(phase, [0, 0, 0])[1]

    def results(self) -> dict:
        """What the check needs, with the engine's arrays copied out."""
        core = self.core
        hg = core.hg
        eng = hg.engine
        e = eng.e
        store = hg.store
        events = [ev for pk in self.hist.participants
                  for ev in store.participant_event_objects(pk, -1)]
        return {"e": e, "complete": True, "events": events,
                "local": core.hex_id(), "syncs": self.gossip["syncs"],
                "eid": dict(hg._eid_of), "blocks": self.blocks,
                "rounds": eng.rounds[:e].copy(),
                "witness": eng.witness[:e].copy(),
                "rr": eng.rr[:e].copy(), "cts": eng.cts_ns[:e].copy(),
                "famous": eng.famous.copy()}

    def close(self):
        self.core.hg.engine.close()


def run(run) -> None:
    from babble_tpu.node.config import Config
    # A program without it cannot run the cell: fail before set-up.
    from babble_tpu.node.node import build_core  # noqa: F401

    cfg, mix = run.config, run.mix
    conf = Config(**cfg["core"])
    if conf.consensus_interval > 0:
        raise ValueError("the core_ingest driver runs a Node's "
                         "consensus_interval 0 path only")
    hist = generate(int(cfg["validators"]), int(mix["job_events"]),
                    int(mix["dag_seed"]), run.seed, int(mix["sync"]),
                    int(mix["tx_bytes"]))

    # Set-up: one whole job compiles every shape a job uses.
    warm = Job(hist, conf)
    try:
        warm.run()
    finally:
        warm.close()
    del warm
    gc.collect()
    run.setup_done()

    ordered = passes = 0
    sums = dict.fromkeys(("verify_ns", "insert_ns", "mirror_ns",
                          "engine_blocked_ns", "verified", "new", "stale",
                          "syncs") + PASS_COUNTS, 0)
    jobs = []
    compiles0 = run.compiles
    t0 = time.monotonic()
    with run.profiled():
        while not jobs or time.monotonic() - t0 < run.seconds:
            job = Job(hist, conf)
            try:
                job.run()
            finally:
                job.close()
            core = job.core
            ordered += core.get_consensus_events_count()
            passes += job.passes
            sums["verify_ns"] += job.phase_total("verify")
            sums["insert_ns"] += job.phase_total("insert")
            sums["mirror_ns"] += job.phase_total("mirror")
            sums["engine_blocked_ns"] += sum(
                job.phase_total("engine_" + p) for p in BLOCKING)
            sums["verified"] += int(core._m_verified.value)
            for k, v in list(job.gossip.items()) + list(job.counts.items()):
                sums[k] += v
            jobs.append(job.results())
            del job, core
            # A Core and its TpuHashgraph hold each other, so the job's
            # engine and its device memory go only at a collection.
            gc.collect()
        t1 = time.monotonic()
        run.window = (t0, t1)
    run.counters.update(
        passes=passes, ordered=ordered, jobs=len(jobs),
        compiles_in_window=run.compiles - compiles0,
        engine_blocked_ns=sums["engine_blocked_ns"],
        mirror_ns=sums["mirror_ns"],
        core={k: sums[k] for k in ("verify_ns", "insert_ns", "verified")},
        gossip={k: sums[k] for k in ("new", "stale", "syncs")},
        **{k: sums[k] for k in PASS_COUNTS})
    run.e2e["ordered_events_per_s"] = ordered / (t1 - t0)
    run.e2e["setup_s"] = run.setup_s
    gc.collect()
    run.read_memory()
    check(run, hist, jobs)
    run.attempted = ordered


def export(hist, events):
    """The store's events in a topological order (parents first, each
    creator's chain in index order), as the reference's arrays, with
    each event's S and transactions."""
    by_hex = {ev.hex(): ev for ev in events}
    pos = {}
    out = []
    for root in sorted(events, key=lambda ev: ev.index()):
        stack = [root]
        while stack:
            ev = stack[-1]
            h = ev.hex()
            if h in pos:
                stack.pop()
                continue
            todo = [by_hex[p] for p in (ev.self_parent(), ev.other_parent())
                    if p and p not in pos]
            if todo:
                stack.extend(todo)
                continue
            pos[h] = len(out)
            out.append(ev)
            stack.pop()
    ids = hist.participants
    return SimpleNamespace(
        events=out,
        sp=np.array([pos.get(ev.self_parent(), -1) for ev in out], np.int32),
        op=np.array([pos.get(ev.other_parent(), -1) for ev in out],
                    np.int32),
        cr=np.array([ids[ev.creator()] for ev in out], np.int32),
        idx=np.array([ev.index() for ev in out], np.int32),
        coin=np.array([middle_bit(ev.hex()) for ev in out], np.int8),
        ts=np.array([ev.body.timestamp.ns for ev in out], np.int64),
        s=[int(ev.s) for ev in out],
        txs=[ev.transactions() for ev in out])


def check(run, hist, jobs) -> None:
    """Each whole job against the reference over the DAG its store
    holds: the engine's consensus, its committed blocks' transactions
    in order, and the set of transactions it committed."""
    n = hist.validators
    totals: dict = {}
    bad_tx = differing = strays = 0
    for j in jobs:
        dag = export(hist, j.pop("events"))
        # What the store holds is what the peers sent, whole, and the
        # local validator's init and one wrap per sync.
        peer = {ev.hex(): (ev.self_parent(), ev.other_parent())
                for ev in dag.events if ev.creator() != j["local"]}
        strays += len(peer.keys() ^ hist.parents.keys())
        strays += sum(peer[h] != hist.parents[h]
                      for h in peer.keys() & hist.parents.keys())
        strays += abs(len(dag.events) - len(peer) - 1 - j["syncs"])
        if len(dag.events) != j["e"]:
            raise RuntimeError(f"the store holds {len(dag.events)} events, "
                               f"the engine {j['e']}")
        ref = reference.consensus(n, dag.sp, dag.op, dag.cr, dag.idx,
                                  dag.coin, dag.ts)
        # The engine's arrays, in the export's order.
        perm = np.array([j["eid"][ev.hex()] for ev in dag.events])
        got = dict(j, **{k: j[k][perm]
                         for k in ("rounds", "witness", "rr", "cts")})
        part = SimpleNamespace(counters={}, checks=[])
        part.check = lambda name, v, lim: part.checks.append((name, v))
        compare(part, [got], ref)
        for name, v in part.checks:
            totals[name] = totals.get(name, 0) + v
        want = [tx for _, txs in reference_blocks.blocks(
            ref.rr, ref.cts, dag.s, dag.txs) for tx in txs]
        have = [tx for b in j["blocks"] for tx in (b.transactions or [])]
        bad_tx += sum(a != b for a, b in zip(have, want))
        bad_tx += abs(len(have) - len(want))
        differing += len(set(have) ^ set(want))
    run.counters["jobs_compared"] = len(jobs)
    for name, v in totals.items():
        run.check(name, v, 0)
    run.check("history_events_differing", strays, 0)
    run.check("block_tx_mismatches", bad_tx, 0)
    run.check("committed_sets_differing", differing, 0)
