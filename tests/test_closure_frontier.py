"""The block-closure coordinates (ops/closure.py) and the round frontier
(ops/frontier.py), as the engine runs them, against the plain
reference (benchmark/reference.py) on random gossip, and against the
host engine (hashgraph/graph.py) where the DAG starts from non-base
roots, which the reference does not take.

Reference semantics anchors: hashgraph.go:448-499 (coordinates),
211-339 + 616-646 (rounds/witnesses)."""

import numpy as np
import pytest

from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
from babble_tpu.hashgraph.root import Root
from babble_tpu.hashgraph.tpu_graph import TpuHashgraph
from babble_tpu.ops.dag import synthetic_dag
from babble_tpu.ops.incremental import IncrementalEngine
from babble_tpu.ops.sim import GossipSim

from fixtures import assert_engine_matches_reference, feed_engine
from test_incremental import _assert_consensus_parity


@pytest.mark.parametrize(
    "n,e,seed", [(4, 60, 0), (8, 300, 1), (16, 1200, 2), (32, 2500, 3)]
)
def test_parity_random_gossip(n, e, seed):
    dag, _ = synthetic_dag(n, e, seed=seed)
    eng = IncrementalEngine(n, capacity=128, block=128, k_capacity=8)
    feed_engine(eng, dag, max(e // 5, n))
    assert_engine_matches_reference(eng, dag)


def test_parity_nonbase_roots():
    """Non-base root rounds (the Reset / start-from-the-middle path,
    reference hashgraph.go:879-898): the engine built with mixed
    per-participant root rounds must seed frontiers above round 0 and
    hold skip-correction candidates back until their true round, as
    the host engine reset to the same roots does."""
    sim = GossipSim(6, seed=5)
    sim.run(144)
    participants = sim.participants
    root_round = [3, 4, 3, 5, 4, 3]
    roots = {pk: Root(round=root_round[pid])
             for pk, pid in participants.items()}
    h = Hashgraph(participants, InmemStore(participants, 10000))
    t = TpuHashgraph(participants, InmemStore(participants, 10000),
                     capacity=64, block=64)
    h.reset(roots)
    t.reset(roots)
    assert list(t.engine.root_round) == root_round
    for ev in sim.events:
        h.insert_event(Event(ev.body, r=ev.r, s=ev.s), True)
        t.insert_event(Event(ev.body, r=ev.r, s=ev.s), True)
    h.divide_rounds()
    h.decide_fame()
    h.find_order()
    t.run_consensus()
    hexes = [ev.hex() for ev in sim.events]
    _assert_consensus_parity(h, t, hexes)
    # Started above base: the first round is the lowest root round + 1.
    assert min(h.round(x) for x in hexes) == min(root_round) + 1
    assert h.store.last_round() >= 6


def test_closure_block_sizes_agree():
    """Block size must not affect results (pure scheduling knob): the
    engine's coordinates and consensus at blocks of 64, 128 and 512."""
    dag, _ = synthetic_dag(8, 300, seed=9)
    engines = [feed_engine(IncrementalEngine(8, capacity=64, block=b,
                                             k_capacity=8), dag, 57)
               for b in (64, 128, 512)]
    e = dag.e
    first = engines[0]
    for eng in engines[1:]:
        assert (np.asarray(eng._la)[:e] == np.asarray(first._la)[:e]).all()
        assert (np.asarray(eng._rb)[:e] == np.asarray(first._rb)[:e]).all()
        for name in ("rounds", "witness", "rr", "cts_ns"):
            assert (getattr(eng, name)[:e] == getattr(first, name)[:e]).all()
        assert (eng.famous == first.famous).all()
    assert_engine_matches_reference(first, dag)
