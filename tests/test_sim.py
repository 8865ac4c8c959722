"""Per-peer view simulation: the checkGossip oracle on the device engine.

- Each simulated peer's ancestry-closed view runs through its own
  IncrementalEngine; all views must produce prefix-compatible
  consensus orders (reference node/node_test.go:548-599).
- A single peer's view must match the incremental host engine fed
  exactly that sub-DAG."""

from __future__ import annotations

import json

import numpy as np

from babble_tpu.hashgraph import Hashgraph, InmemStore
from babble_tpu.hashgraph.event import event_from_json_obj
from babble_tpu.ops.dag import DagTensors
from babble_tpu.ops.incremental import IncrementalEngine
from babble_tpu.ops.sim import (
    GossipSim,
    check_view_consistency,
    simulate_views,
    view_order,
)

from fixtures import feed_engine


def build_sim(n=5, steps=150, seed=3):
    sim = GossipSim(n, seed=seed)
    sim.run(steps)
    return sim


def view_consensus(dag, masks):
    """Run one engine per view (masks [V, E] bool, each view
    ancestry-closed) over the view's own sub-DAG. Returns [V, E] arrays
    (rounds, rr, cts_ns) by global event id, -1 outside the view."""
    v, e = masks.shape
    out = np.full((3, v, e), -1, np.int64)
    for k, mask in enumerate(masks):
        ids = np.flatnonzero(mask)
        local = np.full(e, -1, np.int32)
        local[ids] = np.arange(len(ids))

        def remap(parents):
            p = parents[ids]
            return np.where(p >= 0, local[np.maximum(p, 0)], -1)

        sub = DagTensors(dag.n, len(ids), remap(dag.self_parent),
                         remap(dag.other_parent), dag.creator[ids],
                         dag.index[ids], dag.coin[ids], dag.ts_ns[ids],
                         hexes=[], events=[])
        assert (sub.self_parent < np.arange(len(ids))).all()
        eng = feed_engine(IncrementalEngine(dag.n, capacity=64, block=64,
                                            k_capacity=8), sub, 64)
        out[0, k, ids] = eng.rounds[:len(ids)]
        out[1, k, ids] = eng.rr[:len(ids)]
        out[2, k, ids] = eng.cts_ns[:len(ids)]
    return out


def test_view_consistency_vmap():
    sim = build_sim()
    dag = sim.dag()
    masks = sim.view_masks()
    # add the full view as an extra row: every peer's order must be a
    # prefix-compatible subsequence of the global order too
    masks = np.vstack([masks, np.ones((1, dag.e), dtype=bool)])
    _, rr_v, cts_v = view_consensus(dag, masks)
    orders = check_view_consistency(dag, rr_v, cts_v)
    assert len(orders[-1]) > 0, "full view reached no consensus"
    # at least one partial view decided something
    assert any(len(o) > 0 for o in orders[:-1])


def test_masked_view_matches_host_engine():
    sim = build_sim(n=5, steps=120, seed=11)
    dag = sim.dag()
    masks = sim.view_masks()
    # pick the best-informed peer's view
    v = int(masks.sum(1).argmax())
    mask = masks[v]

    # host engine over exactly that sub-DAG, in insertion order
    sub_events = [ev for i, ev in enumerate(sim.events) if mask[i]]
    h = Hashgraph(sim.participants, InmemStore(sim.participants, 10000))
    for ev in sub_events:
        h.insert_event(event_from_json_obj(json.loads(ev.marshal())), True)
    h.run_consensus()
    host_order = h.consensus_events()

    rounds, rr, cts = (a[0] for a in view_consensus(dag, mask[None, :]))
    dev_order = [dag.hexes[i] for i in view_order(dag, rr, cts)]
    assert dev_order == host_order, "view engine diverges from host engine"

    # per-event round parity within the view
    for i, ev in enumerate(sim.events):
        if mask[i]:
            assert int(rounds[i]) == h.round(ev.hex())


def test_views_with_silent_peers():
    """Up to n - supermajority peers can be silent (the missing-node
    scenario, reference node_test.go:409-420) and the remaining
    supermajority still reaches prefix-consistent consensus."""
    n = 16
    sm = 2 * n // 3 + 1
    silent = np.zeros(n, bool)
    silent[sm:] = True  # n - sm = 5 silent peers
    dag, masks, s_rank = simulate_views(
        n, steps=400, silent=silent, seed=6)
    _, rr_v, cts_v = view_consensus(dag, masks[~silent])
    orders = check_view_consistency(dag, rr_v, cts_v, s_ints=s_rank)
    assert max(len(o) for o in orders) > 50, "silent-peer run decided too little"
    # silent peers' initial events are invisible to the active network
    for sid in np.nonzero(silent)[0]:
        assert not masks[~silent][:, sid].any()
