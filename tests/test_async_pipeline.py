"""Async consensus pipeline parity: the dispatch/collect split with
its double-buffered staging must be invisible in the results.

The seams where silent divergence would hide are (a) appends landing
while a pass is in flight (the second staging buffer), (b) capacity /
chain-bucket regrowth crossing a dispatch boundary, and (c) the
window-overflow redo path re-dispatching from a PendingPass snapshot.
Each test drives those seams and asserts byte-identical consensus
results against an oracle: the plain reference's one-shot computation
over the whole DAG (benchmark/reference.py) for the raw engine, and the
reference-semantics host engine (hashgraph/graph.py) for the full
TpuHashgraph stack.
"""

from __future__ import annotations

import numpy as np
import pytest

from babble_tpu import crypto
from babble_tpu.gojson import Timestamp
from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
from babble_tpu.hashgraph.tpu_graph import TpuHashgraph
from babble_tpu.ops.dag import synthetic_dag
from babble_tpu.ops.incremental import IncrementalEngine

from fixtures import assert_engine_matches_reference


def test_pipelined_engine_matches_one_shot():
    """Interleaved appends (batch k+1 staged while pass k is in
    flight) + forced capacity AND chain-bucket regrowth == the plain
    reference's one-shot computation over the whole DAG, bit for bit."""
    n, e, bs = 8, 420, 48
    dag, _ = synthetic_dag(n, e, seed=11)
    # Tiny engine: event capacity 64 and chain buckets 8 force several
    # regrowths of every device carry mid-stream.
    eng = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    pending = None
    k = 0
    while k < e:
        hi = min(k + bs, e)
        # Appends land BEFORE the previous pass is collected — they go
        # to the fresh staging list while the in-flight pass holds its
        # snapshot (the double-buffer seam under test).
        eng.append_batch(
            dag.self_parent[k:hi], dag.other_parent[k:hi],
            dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
            np.arange(k, hi))
        if pending is not None:
            eng.collect(pending)
        pending = eng.dispatch()
        k = hi
    if pending is not None:
        eng.collect(pending)
    # Drain to fixpoint: the last batch was staged during the final
    # in-flight pass.
    while True:
        pp = eng.dispatch()
        if pp is None:
            break
        eng.collect(pp)

    eng.close()
    assert_engine_matches_reference(eng, dag)


def test_dispatch_collect_contract():
    """API misuse guards: double dispatch raises, collect of a stale
    pass raises, abandon restores the staged batch."""
    n = 4
    dag, _ = synthetic_dag(n, 64, seed=2)
    eng = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    eng.append_batch(dag.self_parent[:32], dag.other_parent[:32],
                     dag.creator[:32], dag.index[:32], dag.coin[:32],
                     np.arange(32))
    pp = eng.dispatch()
    assert pp is not None and eng.inflight
    with pytest.raises(RuntimeError):
        eng.dispatch()
    eng.abandon(pp)
    assert not eng.inflight
    assert eng.backlog() == 32  # batch restored to staging
    with pytest.raises(RuntimeError):
        eng.collect(pp)  # abandoned pass is no longer in flight
    # The restored batch reruns cleanly.
    delta = eng.run()
    assert len(delta.new_rounds) == 32
    eng.close()


def _signed_gossip_events(n_peers, n_events, seed=13):
    """Random-gossip stream of REAL signed events (the shape the node
    runtime produces) plus the participant map."""
    rng = np.random.default_rng(seed)
    keys = [crypto.key_from_seed(5000 + i) for i in range(n_peers)]
    pubs = [crypto.pub_key_bytes(k) for k in keys]
    participants = {"0x" + p.hex().upper(): i for i, p in enumerate(pubs)}
    clock = 1_700_000_000_000_000_000
    heads = [""] * n_peers
    seqs = [-1] * n_peers
    events = []
    creators = np.concatenate([
        np.arange(n_peers),
        rng.integers(0, n_peers, size=n_events - n_peers)])
    others = rng.integers(1, n_peers, size=n_events)
    for i in range(n_events):
        c = int(creators[i])
        op = heads[(c + int(others[i])) % n_peers] if i >= n_peers else ""
        clock += 1_000_000
        seqs[c] += 1
        ev = Event.new([b"tx%d" % i], [heads[c], op], pubs[c], seqs[c],
                       timestamp=Timestamp(clock))
        ev.sign(keys[c])
        heads[c] = ev.hex()
        events.append(ev)
    return events, participants


def test_async_tpu_graph_matches_host_oracle():
    """Byte-identical consensus order vs the host oracle
    (hashgraph/graph.py) with the async pipeline driven the way the
    node's consensus worker drives it: insert a chunk, dispatch,
    insert the next chunk while the pass is in flight, collect. The
    tiny engine capacity forces regrowth across dispatch boundaries."""
    events, participants = _signed_gossip_events(4, 360)

    host = Hashgraph(participants, InmemStore(participants, 100000))
    for ev in events:
        host.insert_event(ev, True)
    host.run_consensus()

    tpu = TpuHashgraph(participants, InmemStore(participants, 100000),
                       capacity=64, block=64, k_capacity=8)
    pending = None
    cs = 60
    for lo in range(0, len(events), cs):
        for ev in events[lo:lo + cs]:
            tpu.insert_event(ev, True)
        if pending is not None:
            tpu.collect_consensus(pending)
        pending = tpu.dispatch_consensus()
    tpu.collect_consensus(pending)
    while True:
        pending = tpu.dispatch_consensus()
        if pending is None:
            break
        tpu.collect_consensus(pending)

    # THE acceptance check: identical consensus order, byte for byte.
    assert tpu.consensus_events() == host.consensus_events()
    # And identical per-event round/round-received on the full stream.
    for ev in events:
        h = ev.hex()
        assert tpu.round(h) == host.round(h)
        assert tpu.round_received(h) == host.round_received(h)
    tpu.engine.close()
