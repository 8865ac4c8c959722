"""The benchmark's signed-ingest cell, v1024-ingest, at a tiny size on
the CPU: its block reference against the host engine, its signed
history's determinism, its driver and readers end to end through the
harness, and Core.sync refusing a forged signature at its position."""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

from babble_tpu.hashgraph import Hashgraph, InmemStore
from babble_tpu.hashgraph.graph import InsertError
from babble_tpu.net.columnar import ColumnarEvents
from benchmark import reference, reference_blocks
from benchmark.drivers.core_ingest import export
from benchmark.signed_history import generate

CELL = "v1024-ingest"
SEED = 2_900_000_123  # past 32 signed bits, as the benchmark's seeds are


@pytest.fixture(scope="module")
def history():
    return generate(16, 1500, 0, SEED, 100)


def harness_config():
    from benchmark import run as harness

    return harness.load_json(
        f"{harness.HERE}/configs/validators1024-signed.json")


def store_events(store, participants):
    return [ev for pk in participants
            for ev in store.participant_event_objects(pk, -1)]


def test_reference_blocks_equal_the_host_engines(history):
    """The reference's consensus, sorted into blocks by the plain block
    reference, commits what the host engine commits, byte for byte."""
    committed = []
    hg = Hashgraph(history.participants,
                   InmemStore(history.participants, 1 << 16),
                   committed.append)
    for batch in history.batches:
        for ev in hg.read_wire_batch(batch):
            hg.insert_event(ev, False)
        hg.run_consensus()
    dag = export(history, store_events(hg.store, history.participants))
    assert len(dag.events) == history.events
    ref = reference.consensus(history.validators, dag.sp, dag.op, dag.cr,
                              dag.idx, dag.coin, dag.ts)
    want = reference_blocks.blocks(ref.rr, ref.cts, dag.s, dag.txs)
    got = [(b.round_received, b.transactions) for b in committed]
    assert len(want) > 3 and got == want
    # The S tiebreak is exercised: some round holds equal timestamps.
    received = np.flatnonzero(ref.rr >= 0)
    keys = {(int(ref.rr[i]), int(ref.cts[i])) for i in received}
    assert len(keys) < len(received)


def test_signed_history_is_a_function_of_its_seeds(history):
    """Two calls give the same wire bytes; another seed other bytes."""
    again = generate(16, 1500, 0, SEED, 100)
    assert again.participants == history.participants
    assert [b.encode() for b in again.batches] == \
        [b.encode() for b in history.batches]
    other = generate(16, 1500, 0, SEED + 1, 100)
    assert other.batches[0].encode() != history.batches[0].encode()


def test_ingest_cell_runs_through_the_harness(monkeypatch):
    """The cell's driver on a 16-validator job in 100-event syncs,
    traced: correct against both references, and every reader of the
    cell's program spans and counters reads something."""
    from benchmark import run as harness

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", harness.CACHE_DIR)
    config = dict(harness.load_json(
        f"{harness.HERE}/configs/validators1024-signed.json"), validators=16)
    mix = dict(harness.load_json(f"{harness.HERE}/traffic/ingest-signed.json"),
               job_events=1500, sync=100)
    run = harness.start(CELL, SEED, 0.5, True, config=config, mix=mix,
                        need_chip=False)
    assert run.correct, run.checks
    names = {name for name, _, _ in run.checks}
    assert {"history_events_differing", "block_tx_mismatches",
            "committed_sets_differing"} <= names
    c = run.counters
    # One pass inline after each sync, as a Node at consensus_interval 0.
    assert c["jobs"] >= 1 and c["passes"] == 15 * c["jobs"]
    assert c["compiles_in_window"] == 0
    assert c["gossip"]["syncs"] == 15 * c["jobs"]
    assert c["gossip"]["new"] == 1500 * c["jobs"]
    assert c["core"]["verified"] == 1500 * c["jobs"]
    assert c["ordered"] > 0
    for name in ("verify_us_per_event", "insert_us_per_event",
                 "mirror_ms_per_pass", "rr_chunks_per_pass",
                 "ts_chunks_per_pass"):
        assert run.metrics[name]["value"] > 0, name
    # A loaded CPU may leave no idle gap under a sync: 0 is a reading.
    assert run.metrics["device_idle_ms_per_pass.core_sync"]["value"] >= 0


def test_forged_signature_is_refused_at_its_position(history):
    """A sync whose 38th event carries a corrupted S raises at that
    event; the 37 before it are inserted, it and the rest are not."""
    from babble_tpu.node.config import Config
    from babble_tpu.node.node import build_core
    from babble_tpu.telemetry import ClusterClock, Registry, SpanRing

    conf = Config(engine="tpu", cache_size=1 << 16)
    core = build_core(conf, history.local_id, history.local_key,
                      InmemStore(history.participants, conf.cache_size),
                      None, trace=SpanRing(0), registry=Registry(),
                      clock=ClusterClock())
    try:
        core.init()
        good = history.batches[0]
        k = 37
        sigs = bytearray(good.sigs)
        sigs[64 * k + 63] ^= 1  # the last byte of event k's S
        forged = ColumnarEvents(*(getattr(good, f) for f in good.__slots__))
        forged.sigs = bytes(sigs)
        hexes = [ev.hex() for ev in Hashgraph(
            history.participants,
            InmemStore(history.participants, 1 << 16)).read_wire_batch(good)]
        with pytest.raises(InsertError, match="Invalid signature"):
            core.sync(forged)
        store = core.hg.store
        assert all(store.has_event(h) for h in hexes[:k])
        assert not any(store.has_event(h) for h in hexes[k:])
        assert core.hg.engine.e == 1 + k
    finally:
        core.hg.engine.close()


def test_check_holds_the_store_to_the_history(history):
    """A job's store is held to the history it was sent: one history
    event changed (its parents) and one missing are both counted."""
    from benchmark.drivers import core_ingest
    from babble_tpu.node.config import Config

    conf = Config(**dict(harness_config()["core"], consensus_interval=0))
    job = core_ingest.Job(history, conf)
    try:
        job.run()
    finally:
        job.close()
    res = job.results()

    def strays(hist):
        checks = {}
        run = SimpleNamespace(counters={}, check=lambda name, v, lim:
                              checks.__setitem__(name, v))
        core_ingest.check(run, hist, [dict(res, events=list(res["events"]))])
        return checks

    assert set(strays(history).values()) == {0}
    parents = dict(history.parents)
    first, last = list(parents)[:2]
    parents[first] = (parents[first][0], "0xFORGED")
    del parents[last]
    got = strays(dataclasses.replace(history, parents=parents))
    assert got["history_events_differing"] == 2
