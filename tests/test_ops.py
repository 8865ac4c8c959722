"""The device engine on the reference fixture graphs, through
TpuHashgraph, against the incremental host engine (itself asserted
against the reference fixtures in test_hashgraph.py): coordinates, and
the reference's own expectations for the funky graph. Rounds, fame,
order and blocks on the same graphs are test_incremental.py's
test_tpu_graph_matches_host."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph.tpu_graph import TpuHashgraph
from babble_tpu.ops import incremental as inc

from fixtures import build_basic_graph, build_funky_graph


def tpu_twin(b):
    """A TpuHashgraph fed a fixture's events, consensus run once."""
    participants = b.participants()
    t = TpuHashgraph(participants, InmemStore(participants, 10000),
                     capacity=64, block=64)
    for ev in b.ordered_events:
        t.insert_event(ev, True)
    t.run_consensus()
    return t


def test_coordinates_parity_basic():
    """The ancestry fixture exercises coordinates (reference
    hashgraph_test.go:66-133): the engine's resident last ancestors and
    its first descendants, read from the rank cube, equal the host's."""
    h, b = build_basic_graph()
    t = tpu_twin(b)
    eng = t.engine
    ids = np.array([t._eid_of[ev.hex()] for ev in b.ordered_events])
    la = np.asarray(eng._la)[ids]
    fd = np.asarray(inc._FdRows(eng._ranks, jnp.asarray(eng.chain_len),
                                eng._cr_d, eng._idx_d)[jnp.asarray(ids)])
    for k, ev in enumerate(b.ordered_events):
        host_ev = h.store.get_event(ev.hex())
        assert la[k].tolist() == [c.index for c in host_ev.last_ancestors], (
            f"last_anc mismatch for {b.get_name(ev.hex())}"
        )
        assert fd[k].tolist() == [c.index for c in host_ev.first_descendants], (
            f"first_desc mismatch for {b.get_name(ev.hex())}"
        )


def test_funky_reference_asserts():
    """Re-assert the reference's funky-fixture expectations directly
    against the device engine (hashgraph_test.go:1539-1588)."""
    h, b = build_funky_graph()
    t = tpu_twin(b)
    assert max(t.round(ev.hex()) for ev in b.ordered_events) == 5
    assert t.undecided_rounds == [4, 5]
    # exact per-block tx counts from the reference test
    expected_tx_counts = {1: 6, 2: 7, 3: 7}
    for rr, n_txs in expected_tx_counts.items():
        blk = t.store.get_block(rr)
        assert len(blk.transactions or []) == n_txs, f"block {rr}"
