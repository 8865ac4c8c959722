"""Coin-round fame decisions — host and device must agree, and the coin
value must be observably load-bearing.

The reference decides fame through a coin flip when a vote round hits
diff % n == 0 without a supermajority tally: each voter adopts the
middle bit of its own hash (hashgraph.go:695-709, middleBit 1039-1048).
Real coin bits depend on event signatures (Event.Hash covers R/S,
event.go:170-180), which are not deterministic across builds — so these
tests force the coin to each constant and assert the
topology-determined outcomes of both worlds. A sign flip anywhere in
the coin path (middle_bit itself, the host's coin-round vote, or the
device kernel's `coin_vote`) swaps or breaks one of the worlds.
"""

from __future__ import annotations

import copy

import pytest

import babble_tpu.hashgraph.graph as graph_mod
import babble_tpu.hashgraph.tpu_graph as tpu_mod
from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph.graph import middle_bit
from babble_tpu.hashgraph.round_info import Trilean
from babble_tpu.hashgraph.tpu_graph import TpuHashgraph

from fixtures import build_coin_graph

CACHE = 10000


def test_middle_bit_vectors():
    """Pin the coin function itself (reference hashgraph.go:1039-1048:
    False iff the middle byte of the hash is zero)."""
    assert middle_bit("0x00") is False
    assert middle_bit("0x" + "AB" * 16 + "00" + "AB" * 15) is False
    assert middle_bit("0x" + "00" * 16 + "80" + "00" * 15) is True
    assert middle_bit("0x" + "FF" * 32) is True


@pytest.fixture(scope="module")
def coin_builder():
    return build_coin_graph()


def _host_run(b, const):
    events = copy.deepcopy(b.ordered_events)
    h = b.make_hashgraph(InmemStore(b.participants(), CACHE))
    for ev in events:
        h.insert_event(ev, True)
    calls = []

    def forced(hx):
        calls.append(hx)
        return bool(const)

    orig = graph_mod.middle_bit
    graph_mod.middle_bit = forced
    try:
        h.divide_rounds()
        h.decide_fame()
        h.find_order()
    finally:
        graph_mod.middle_bit = orig
    return h, calls


def _device_run(b, const):
    events = copy.deepcopy(b.ordered_events)
    t = TpuHashgraph(b.participants(), InmemStore(b.participants(), CACHE),
                     capacity=64, block=64)
    orig_t, orig_g = tpu_mod.middle_bit, graph_mod.middle_bit
    tpu_mod.middle_bit = lambda hx: bool(const)
    graph_mod.middle_bit = lambda hx: bool(const)
    try:
        for ev in events:
            t.insert_event(ev, True)
        t.run_consensus()
    finally:
        tpu_mod.middle_bit = orig_t
        graph_mod.middle_bit = orig_g
    return t


def test_coin_true_world_decides_through_coin(coin_builder):
    """Coin forced to 1: round-4 voters flip coins for w00 (two voters
    lack a supermajority tally at diff=4), and round 5 decides w00
    famous from those coin votes."""
    b = coin_builder
    h, calls = _host_run(b, 1)
    assert len(calls) == 2, "expected exactly two coin votes"
    # the coin voters are round-4 witnesses voting about round 0
    assert sorted(h.round(y) for y in calls) == [4, 4]
    r0 = h.store.get_round(0)
    assert r0.events[b.index["w00"]].famous == Trilean.TRUE
    assert h.undecided_rounds == [4, 5]
    assert h.last_consensus_round == 3
    assert len(h.consensus_events()) == 20


def test_coin_false_world_stalls(coin_builder):
    """Coin forced to 0: the same tally never reaches a supermajority,
    w00 stays UNDEFINED forever and round 0 never decides — the
    hashgraph coin-round liveness hole, observable and deterministic."""
    b = coin_builder
    h, calls = _host_run(b, 0)
    assert len(calls) == 4  # diff=4 and diff=8 coin rounds both consulted
    r0 = h.store.get_round(0)
    assert r0.events[b.index["w00"]].famous == Trilean.UNDEFINED
    assert 0 in h.undecided_rounds
    assert h.last_consensus_round == 3  # rounds 1-3 decided regardless
    assert h.consensus_events() == []


@pytest.mark.parametrize("const", [0, 1], ids=["coin0", "coin1"])
def test_coin_world_device_parity(coin_builder, const):
    """The device kernel's coin tensor path (kernels.decide_fame_impl
    coin_vote) must reproduce the host's coin-world outcome exactly —
    a sign flip in either engine breaks one of the two worlds."""
    b = coin_builder
    h, _ = _host_run(b, const)
    t = _device_run(b, const)
    w00 = b.index["w00"]
    assert (t.store.get_round(0).events[w00].famous
            == h.store.get_round(0).events[w00].famous)
    assert t.last_consensus_round == h.last_consensus_round
    assert t.consensus_events() == h.consensus_events()
    # full fame-table parity over every round
    for r in range(h.store.last_round() + 1):
        hr = h.store.get_round(r)
        tr = t.store.get_round(r)
        assert set(hr.witnesses()) == set(tr.witnesses()), f"round {r}"
        for w in hr.witnesses():
            assert hr.events[w].famous == tr.events[w].famous, (
                f"round {r} witness {w[:12]}")
